"""Chip smoke test: the device-resident placement stream on one TPU.

Serves the paper's deployment through ``PlacementRuntime.serve_stream(...,
array_backend="jax")`` — the 19 Lambda memory configs, the bench's
3-device heterogeneous edge fleet, a 1,048,576-task IR Poisson stream at
the app's 4 tasks/s in 65,536-row chunks under ``MinLatencyPolicy`` — and
checks it against the numpy oracle serving the same stream with the GBRT
route pinned to the numpy tree walk (so the reference never runs the kernel
under test):

- identical ``target_codes`` on every record;
- every chunk resident and compiled, one state sync, no fallback, the GBRT
  Pallas kernel on the path and not interpreted;
- a same-shape continuation stream on the same engine leaves the jit caches
  unchanged;
- a one-chunk ``MinCostPolicy`` stream, checked against its own oracle.

Run from the checkout root on a machine with one TPU chip:

    python3 chip_smoke.py

The last line of standard output is the JSON verdict; every failed check
exits non-zero. Without a TPU the script exits non-zero before serving.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

N_TASKS = 1 << 20
CHUNK = 1 << 16
CONT_CHUNKS = 2                 # continuation stream length, in chunks
FLEET = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}   # bench_runtime's fleet
C_MAX, ALPHA = 5e-6, 0.05       # MinLatency: a budget that binds (Alg. 1)
DEADLINE_MS = 1500.0            # MinCost deadline
FLOAT_COLS = ("predicted_latency_ms", "predicted_cost")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def stream(twin, n: int, seed: int, start_ms: float = 0.0):
    """``n`` IR tasks at the app's Poisson rate, arrivals from ``start_ms``,
    as one columnar chunk (``serve_stream`` slices it)."""
    from repro.core.workload import TaskChunk

    c = next(twin.poisson(seed).chunks(n, chunk_size=n))
    return TaskChunk(idx=c.idx, arrival_ms=c.arrival_ms + start_ms,
                     size=c.size, bytes=c.bytes)


def runtime(twin, models, policy):
    from repro.core.apps import MEMORY_CONFIGS_MB
    from repro.core.decision import DecisionEngine
    from repro.core.fit import build_fleet_predictor
    from repro.core.runtime import PlacementRuntime, TwinBackend

    pred = build_fleet_predictor(models, dict(FLEET),
                                 configs=MEMORY_CONFIGS_MB)
    return PlacementRuntime(
        DecisionEngine(predictor=pred, policy=policy),
        TwinBackend(twin, seed=11, edge_names=tuple(FLEET),
                    edge_speed=FLEET))


def serve_reference(twin, models, policy, tasks, chunk: int):
    """The numpy oracle with the GBRT route pinned to the tree walk."""
    from repro.core import predictor as predictor_mod

    was = predictor_mod.GBRT_KERNEL_MODE
    predictor_mod.GBRT_KERNEL_MODE = "off"
    try:
        return runtime(twin, models, policy).serve_stream(
            tasks, chunk_size=chunk, array_backend="numpy")
    finally:
        predictor_mod.GBRT_KERNEL_MODE = was


def compare(ref, res) -> dict:
    """Decision identity and float agreement of two served streams."""
    a, b = ref.records, res.records
    _check(len(a) == len(b), f"record counts differ: {len(a)} vs {len(b)}")
    _check(tuple(a.target_names) == tuple(b.target_names),
           "target tables differ")
    bad = np.flatnonzero(a.target_codes != b.target_codes)
    err = {}
    for col in FLOAT_COLS:
        x = np.asarray(getattr(a, col), np.float64)
        y = np.asarray(getattr(b, col), np.float64)
        fin = np.isfinite(x) & (x != 0)
        err[col] = float(np.max(np.abs(y[fin] - x[fin]) / np.abs(x[fin]))) \
            if fin.any() else 0.0
    return {"n": len(a), "mismatched": int(bad.size),
            "first_diverging_row": int(bad[0]) if bad.size else None,
            "max_rel_err": err}


def _served(rt, tasks, chunk: int):
    t0 = time.perf_counter()
    res = rt.serve_stream(tasks, chunk_size=chunk, array_backend="jax")
    return res, time.perf_counter() - t0     # serve_stream ends in a sync


def check_stream(rt, chunks: int) -> dict:
    """Counters of a device-resident stream: every chunk resident and
    compiled, one sync, no fallback, the GBRT kernel on the path."""
    s = rt.stream_stats
    r = s["residency"]
    js = rt.engine.jax_stats
    _check(s["chunks"] == chunks, f"{s['chunks']} chunks, expected {chunks}")
    _check(s["walked"] == 0, f"{s['walked']} rows walked on the host")
    _check(r["resident_chunks"] == chunks,
           f"resident_chunks={r['resident_chunks']} of {chunks}")
    _check(r["state_syncs"] == 1, f"state_syncs={r['state_syncs']}")
    _check(r["fallback_syncs"] == 0, f"fallback_syncs={r['fallback_syncs']}")
    _check(r["fallback_chunks"] == 0,
           f"fallback_chunks={r['fallback_chunks']}")
    _check(not js["interpret"], "core ran in interpret mode")
    _check(js["gbrt_kernel"], "GBRT kernel not on the path")
    return r


def smoke(n_tasks: int = N_TASKS, chunk: int = CHUNK, start_ms: float = 0.0,
          cont_chunks: int = CONT_CHUNKS, seed: int = 1,
          log=print) -> dict:
    """The serving body: MinLatency stream + continuation + one MinCost
    chunk, each checked against the numpy oracle. Raises ``SmokeFailure``
    on any failed check; returns the report."""
    from repro.core import jax_core
    from repro.core.apps import MEMORY_CONFIGS_MB
    from repro.core.decision import MinCostPolicy, MinLatencyPolicy
    from repro.core.fit import fit_app

    t0 = time.perf_counter()
    twin, models = fit_app("IR", seed=0, configs=MEMORY_CONFIGS_MB)
    tasks = stream(twin, n_tasks, seed, start_ms)
    log(f"fit + workload: {time.perf_counter() - t0:.3f} s host; "
        f"{n_tasks} IR tasks, arrivals {tasks.arrival_ms[0]:.6f}.."
        f"{tasks.arrival_ms[-1]:.6f} ms, {len(MEMORY_CONFIGS_MB)} configs, "
        f"{len(FLEET)} edge devices")
    n_chunks = -(-n_tasks // chunk)
    report: dict = {}

    # ---- MinLatency stream, 19 configs ------------------------------------
    minlat = lambda: MinLatencyPolicy(c_max=C_MAX, alpha=ALPHA)  # noqa: E731
    t0 = time.perf_counter()
    ref = serve_reference(twin, models, minlat(), tasks, chunk)
    log(f"numpy oracle: {time.perf_counter() - t0:.3f} s host")
    rt = runtime(twin, models, minlat())
    res, dt = _served(rt, tasks, chunk)
    cmp = compare(ref, res)
    log(f"minlat stream: {dt:.3f} s host (compile included), "
        f"{cmp['mismatched']} mismatched decisions of {cmp['n']}, first "
        f"diverging row {cmp['first_diverging_row']}, max rel err "
        f"{cmp['max_rel_err']}")
    counts = np.bincount(res.records.target_codes,
                         minlength=len(res.records.target_names))
    log(f"minlat targets: {dict(zip(res.records.target_names, counts.tolist()))}")
    _check(cmp["mismatched"] == 0,
           f"{cmp['mismatched']} MinLatency decisions differ from the "
           f"oracle (first at row {cmp['first_diverging_row']})")
    r = check_stream(rt, n_chunks)
    log(f"minlat residency: {r}; jax_stats: {rt.engine.jax_stats}")
    report["minlat"] = {**cmp, "host_s": dt, "residency": r}

    # ---- same-shape continuation: no retrace ------------------------------
    if cont_chunks:
        core = jax_core.core_for(rt.engine)
        before = core.compile_stats()
        cont = stream(twin, cont_chunks * chunk, seed + 1,
                      float(tasks.arrival_ms[-1]))
        _, dt = _served(rt, cont, chunk)
        after = core.compile_stats()
        log(f"continuation: {cont_chunks} chunks, {dt:.3f} s host, "
            f"compile_stats {before} -> {after}")
        _check(after == before, f"continuation retraced: {before} -> {after}")
        check_stream(rt, cont_chunks)
        report["continuation"] = {"host_s": dt, "compile_stats": after}

    # ---- one MinCost chunk --------------------------------------------------
    one = stream(twin, chunk, seed + 2, start_ms)
    mincost = lambda: MinCostPolicy(deadline_ms=DEADLINE_MS)  # noqa: E731
    ref = serve_reference(twin, models, mincost(), one, chunk)
    rt = runtime(twin, models, mincost())
    res, dt = _served(rt, one, chunk)
    cmp = compare(ref, res)
    log(f"mincost chunk: {dt:.3f} s host (compile included), "
        f"{cmp['mismatched']} mismatched decisions of {cmp['n']}, max rel "
        f"err {cmp['max_rel_err']}")
    _check(cmp["mismatched"] == 0,
           f"{cmp['mismatched']} MinCost decisions differ from the oracle "
           f"(first at row {cmp['first_diverging_row']})")
    check_stream(rt, 1)
    report["mincost"] = {**cmp, "host_s": dt}
    return report


def main() -> int:
    from repro import compile_cache

    cache = compile_cache.configure()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}; compile cache: {cache}")
    smoke()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
