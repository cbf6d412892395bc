"""Roofline analysis from the dry-run's compiled artifacts (§Roofline).

Hardware constants come from ``PEAKS``, keyed by the ``device_kind`` the
cell was compiled for; a device without published peaks is an error. For
each (arch × shape × mesh) cell recorded by ``repro.launch.dryrun`` this
derives:

    compute term    = HLO_FLOPs(dev)        / peak_FLOPs
    memory term     = HLO_bytes(dev)        / HBM_bw
    collective term = collective_bytes(dev) / link_bw

(the dry-run HLO is the post-GSPMD per-device program, so all numbers are
per-device already), plus MODEL_FLOPS = 6·N_active·tokens (train) or
2·N_active·tokens (inference), the useful-compute ratio, the dominant term,
and the roofline fraction = useful-compute time / dominant term.
"""

from __future__ import annotations

import glob
import json
import os
import time

from benchmarks.common import banner

# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s of interconnect (4 links, 50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to benchmarks/roofline.py PEAKS with their source")
    return PEAKS[device_kind]

TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,       # one token per sequence per step
    "long_500k": 1,
}

ADVICE = {
    "compute": "raise MFU: larger per-step tiles, fuse elementwise into dots, "
               "cut remat recompute",
    "memory": "cut HBM traffic: better fusion/layout, bf16 activations, "
              "avoid full-logit materialization",
    "collective": "cut link bytes: reshard (reduce-scatter instead of "
                  "all-reduce), overlap collectives with compute, shard "
                  "activations over fewer TP ops, gradient compression "
                  "across pods",
}


def analyze_cell(d: dict) -> dict:
    hlo = d["hlo"]
    kind = d["kind"]
    devices = d["devices"]
    n_active = d.get("active_param_count") or d["param_count"]
    tokens = TOKENS[d["shape"]]
    mult = 6.0 if kind == "train" else 2.0
    model_flops_dev = mult * n_active * tokens / devices
    peak = peaks(d.get("device_kind"))

    t_c = hlo["flops"] / peak["flops"]
    t_m = hlo["hbm_bytes"] / peak["hbm_bw"]
    t_l = hlo["collective_link_bytes"] / peak["link_bw"]
    terms = {"compute": t_c, "memory": t_m, "collective": t_l}
    dom = max(terms, key=terms.get)
    bound = terms[dom]
    useful_t = model_flops_dev / peak["flops"]
    return {
        "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
        "kind": kind,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_l,
        "dominant": dom,
        "model_flops_dev": model_flops_dev,
        "useful_ratio": model_flops_dev / max(hlo["flops"], 1e-9),
        "roofline_frac": useful_t / max(bound, 1e-12),
        "peak_gib": d.get("memory", {}).get("peak_bytes_estimate", 0) / 2**30,
        "advice": ADVICE[dom],
    }


def load_cells(dryrun_dir: str = "experiments/dryrun", mesh: str = "pod",
               tag: str = "") -> list[dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(dryrun_dir, f"*_{mesh}{tag}.json"))):
        base = os.path.basename(f)
        if not tag and not base.endswith(f"_{mesh}.json"):
            continue  # don't match tagged variants when untagged requested
        with open(f) as fh:
            out.append(analyze_cell(json.load(fh)))
    return out


def run(emit, mesh: str = "pod"):
    banner(f"Roofline — per (arch × shape), {mesh} mesh "
           "(terms in ms/step/device)")
    cells = load_cells(mesh=mesh)
    if not cells:
        print("no dry-run artifacts found — run "
              "`python -m repro.launch.dryrun --all` first")
        return
    print(f"{'arch':>26} {'shape':<12} {'comp ms':>9} {'mem ms':>8} "
          f"{'coll ms':>8} {'bound':<10} {'useful':>7} {'roofline':>9}")
    t0 = time.perf_counter()
    for c in cells:
        print(f"{c['arch']:>26} {c['shape']:<12} "
              f"{c['compute_s']*1e3:>9.2f} {c['memory_s']*1e3:>8.2f} "
              f"{c['collective_s']*1e3:>8.2f} {c['dominant']:<10} "
              f"{c['useful_ratio']:>6.1%} {c['roofline_frac']:>8.1%}")
        emit(f"roofline/{c['arch']}/{c['shape']}/{mesh}",
             (time.perf_counter() - t0) * 1e6 / max(len(cells), 1),
             f"dominant={c['dominant']};roofline={c['roofline_frac']:.3f}"
             f";useful={c['useful_ratio']:.3f}")
    # summary: dominant-term histogram
    hist: dict[str, int] = {}
    for c in cells:
        hist[c["dominant"]] = hist.get(c["dominant"], 0) + 1
    print(f"\ndominant-term histogram: {hist}")
    worst = sorted(cells, key=lambda c: c["roofline_frac"])[:3]
    print("worst roofline fractions (hillclimb candidates):")
    for c in worst:
        print(f"  {c['arch']} {c['shape']}: {c['roofline_frac']:.1%} "
              f"({c['dominant']}-bound) → {c['advice']}")


def write_markdown(path: str = "experiments/roofline.md"):
    """EXPERIMENTS.md §Roofline source table (both meshes)."""
    lines = ["| arch | shape | mesh | compute ms | memory ms | collective ms "
             "| dominant | useful | roofline | peak GiB |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    for mesh in ("pod", "multipod"):
        for c in load_cells(mesh=mesh):
            lines.append(
                f"| {c['arch']} | {c['shape']} | {c['mesh']} "
                f"| {c['compute_s']*1e3:.2f} | {c['memory_s']*1e3:.2f} "
                f"| {c['collective_s']*1e3:.2f} | {c['dominant']} "
                f"| {c['useful_ratio']:.1%} | {c['roofline_frac']:.1%} "
                f"| {c['peak_gib']:.2f} |")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


if __name__ == "__main__":
    from benchmarks.common import CsvSink

    sink = CsvSink()
    run(sink)
    run(sink, mesh="multipod")
    print("\nwrote", write_markdown())
    print(sink.dump())
