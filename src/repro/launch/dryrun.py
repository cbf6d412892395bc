import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks device count on first init).
# The dry-run proves the production distribution config is coherent without
# hardware: for every (architecture × shape × mesh) cell it lowers + compiles
# the real step function against ShapeDtypeStruct inputs, then records
# memory_analysis / cost_analysis / collective-bytes for §Dry-run + §Roofline.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import ARCHS, SHAPES, applicable_shapes, get_config  # noqa: E402
from repro.distributed.sharding import sharding_ctx                     # noqa: E402
from repro.launch.hlo_analysis import analyze_compiled, save_json       # noqa: E402
from repro.launch.mesh import make_production_mesh                      # noqa: E402
from repro.launch.steps import build_cell                               # noqa: E402

OUT_DIR_DEFAULT = "experiments/dryrun"
# the chip the production mesh models (one v5e pod, ``repro.launch.mesh``)
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = OUT_DIR_DEFAULT, overrides: dict | None = None,
             tag: str = "") -> dict:
    """Lower + compile one cell on the production mesh; dump analyses."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_updates(**overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multipod" if multi_pod else "pod"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(cfg, shape, mesh)

    with mesh, sharding_ctx(mesh, cell.rules):
        jitted = jax.jit(cell.step, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    result = {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": mesh_name, "devices": int(mesh.size), "fsdp": cell.fsdp,
        "device_kind": TARGET_DEVICE_KIND,
        "param_count": cell.model.param_count(),
        "active_param_count": getattr(cell.model, "active_param_count",
                                      cell.model.param_count)(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    result.update(analyze_compiled(compiled))
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}_{shape_name}_{mesh_name}{tag}.json"
    save_json(os.path.join(out_dir, fname), result)
    return result


def _fmt(result: dict) -> str:
    mem = result.get("memory", {})
    peak = mem.get("peak_bytes_estimate", 0) / 2**30
    coll = result.get("hlo", {}).get("collective_link_bytes", 0) / 2**30
    fl = result.get("hlo", {}).get("flops", 0) / 1e12
    return (f"{result['arch']:>26s} {result['shape']:<12s} {result['mesh']:<8s} "
            f"{result['kind']:<7s} peak/dev={peak:7.2f} GiB  "
            f"flops/dev={fl:9.3f} T  coll/dev={coll:7.3f} GiB  "
            f"compile={result['compile_s']:6.1f}s")


def iter_cells(archs=None, shapes=None):
    for arch in (archs or sorted(ARCHS)):
        cells = applicable_shapes(get_config(arch))
        for sname, s in cells.items():
            if shapes and sname not in shapes:
                continue
            yield arch, sname, s is None  # (arch, shape, skipped)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", action="append", help="architecture id(s)")
    p.add_argument("--shape", action="append", choices=sorted(SHAPES),
                   help="shape cell(s)")
    p.add_argument("--mesh", choices=("pod", "multipod", "both"), default="both")
    p.add_argument("--all", action="store_true", help="all 40 cells")
    p.add_argument("--out-dir", default=OUT_DIR_DEFAULT)
    p.add_argument("--list", action="store_true", help="list cells and exit")
    p.add_argument("--set", action="append", default=[], metavar="K=V",
                   help="ArchConfig override for §Perf variants, e.g. "
                        "--set sp_acts=true --set microbatch=4")
    p.add_argument("--tag", default="", help="suffix for variant JSON files")
    args = p.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v

    archs = args.arch or (sorted(ARCHS) if args.all else None)
    if archs is None:
        p.error("pass --arch <id> (repeatable) or --all")
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    if args.list:
        for arch, sname, skipped in iter_cells(archs, args.shape):
            print(f"{arch:>26s} {sname:<12s} {'SKIP (documented)' if skipped else 'run'}")
        return 0

    failures, n_run, n_skip = [], 0, 0
    for arch, sname, skipped in iter_cells(archs, args.shape):
        if skipped:
            n_skip += 1
            print(f"{arch:>26s} {sname:<12s} SKIP (documented: "
                  f"{'encoder-only' if get_config(arch).is_encoder_only else 'needs sub-quadratic attention'})")
            continue
        for mp in meshes:
            try:
                res = run_cell(arch, sname, multi_pod=mp, out_dir=args.out_dir,
                               overrides=overrides or None, tag=args.tag)
                print(_fmt(res), flush=True)
                n_run += 1
            except Exception:
                failures.append((arch, sname, "multipod" if mp else "pod"))
                print(f"{arch:>26s} {sname:<12s} {'multipod' if mp else 'pod':<8s} "
                      f"FAILED:\n{traceback.format_exc()}", flush=True)

    print(f"\ndry-run: {n_run} compiled, {n_skip} documented skips, "
          f"{len(failures)} failures")
    for f in failures:
        print(f"  FAILED: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
