"""Runtime throughput benchmarks: the columnar decision core, vectorized twin
execution, end-to-end serve, and the edge-fleet scenario.

Sections (run all via ``python benchmarks/run.py --only runtime``, or this
file directly; ``--smoke`` on run.py exercises the parity-critical sections in
seconds for CI; ``--json`` writes the machine-readable ``BENCH_runtime.json``):

1. **decision** — the columnar ``place_many`` (vectorized policy kernels +
   speculate-and-repair, ISSUE-3) vs the per-task decision walk over the same
   batched predictions (the pre-columnar ``place_many``) vs the per-task
   ``place()`` loop, on the 100k-task saturated-fleet workload. Decisions
   must be identical across all three; columnar ≥ 10x the walk (acceptance
   bar) and far above the step loop. A mixed edge/cloud budget is also
   reported (repairs are denser there, so the ratio is lower).
2. **serve** — end-to-end ``PlacementRuntime.serve`` on the same scenario:
   the array-native path (``DecisionBatch`` → ``execute_many`` →
   ``RecordBatch``) vs the legacy object path (walk decisions + per-task
   outcome/record objects); bit-identical results, ≥ 5x (acceptance bar).
3. **twin-exec** — vectorized ``TwinBackend.execute_many`` vs the sequential
   ``execute`` loop on a 100k-task saturated-fleet workload (3 edge devices,
   bursty arrivals, edge-first budget). Outcomes must be bit-identical —
   ``execute_many`` consumes the same RNG streams — and throughput ≥ 10x.
   A mixed edge/cloud split is also reported (the cloud container-pool walk
   is inherently sequential, so its ratio is lower).
4. **fleet** — skewed (bursty) arrivals on a heterogeneous 3-device fleet:
   least-predicted-wait balancing must beat round-robin, and the fleet must
   beat the single-edge configuration on mean end-to-end latency. Per-device
   utilization/queue-wait summaries show the balance.
5. **async-overlap** — the live event-driven driver (ISSUE 4):
   ``serve_async`` over the REAL executor pool on a saturated 3-device edge
   fleet with emulated WAN result-upload legs (``NetworkProfile`` — genuine
   wall-clock waits standing in for the paper's network legs) vs the
   sequential live driver on the identical workload. Wall-clock overlap
   speedup must clear the floor (≥ 2x full, relaxed in smoke): per-device
   worker threads hide each other's network waits and interleave compute up
   to the local core budget. Real compiles + real executions; identical task
   counts and placement on both sides.
6. **million** — the 1M-task columnar scenario (full runs only): previously
   impractical (minutes of per-task object churn); now end-to-end serve in
   seconds, entirely on arrays.
7. **streaming-scale** (ISSUE 5) — ``serve_stream`` at 10M tasks: arrival
   chunks through the columnar pipeline with a ``RecordArena`` result,
   O(chunk) working set instead of the one-shot path's O(n × targets)
   prediction matrices. Asserts a peak-RSS ceiling (full) / tracemalloc
   ceiling (smoke) AND a throughput floor ≥ the one-shot serve rate measured
   in the same run. Plus **sharded**: ``serve_sharded`` running the IR+FD+STT
   application mix as parallel shards (threads and the process fallback) vs
   sequential per-app serves — per-record parity asserted across all modes;
   the ≥2x wall-clock floor is asserted on machines with ≥ 4 cores (CPU-bound
   shards cannot physically exceed ~1x on the 2-core CI class, where the
   parity check is the bench's value; the measured speedup is reported
   either way).
8. **trace-planner** (ISSUE 6) — replaying a recorded 50k-task trace
   (``repro.trace.TraceWorkload``) must match the equivalent in-memory
   stream per record AND land within 1.2x of its wall time (replay slices
   arrays instead of sampling); plus an 8-candidate what-if capacity search
   (``repro.planner``, successive halving over fleet sizes × policies) whose
   winner must be the cheapest SLO-meeting config, verified on the full
   trace.
9. **jax-core** (ISSUE 7) — the device-resident predict→place pipeline
   (``repro.core.jax_core``) vs the numpy columnar path. Full: a 1M-task
   steady stream served with ``array_backend="jax"``; on an accelerator the
   device core must clear ≥ 2x the numpy rate (on CPU the measured ratio is
   report-only — XLA's sequential-scan overhead dominates there, the
   decision-equality assertion is the CPU value). Smoke: a small-N parity
   gate — ``"jax_interpret"`` bit-identical per record to the oracle,
   compiled ``"jax"`` decision-identical — plus the compile-cache check:
   after a warmup serve, a second same-shape stream must NOT retrace
   (``JaxPlacementCore.compile_stats()`` stable). Both variants also time
   ``SCAN_MODE="seq"`` vs ``"assoc"`` on compiled streams and audit the
   ``"auto"`` table (``jax_core._AUTO_SCAN``) against the measured winner —
   asserted at full size on accelerators, report-only row on CPU.
10. **chaos** (ISSUE 8) — the deterministic fault-injection layer. Faults-off
    overhead: retry + breaker + admission armed over an EMPTY ``FaultSpec``
    must be bit-identical per record to the plain serve AND within 3% of its
    rate at full size (relaxed in smoke; the parity gate never is).
    Degradation: 1 of 3 edge devices down for the middle 30% of the run plus
    a flaky cloud config — retry/failover/breaker/shedding must carry the
    top (non-sheddable) SLO tier to ≥99% attainment.
11. **residency** (ISSUE 9) — persistent device-resident streaming. A steady
    compiled stream keeps CIL pools / surplus / horizons device-side across
    chunks: every chunk must place resident (zero per-chunk host commits,
    zero fallback syncs, at most the one stream-end materialization), stay
    decision-identical to the per-chunk ``device_residency=False`` path and,
    on an accelerator, beat its rate. A hedged chunk mid-stream must cost
    exactly ONE extra (fallback) sync with residency re-entered afterwards.

    PYTHONPATH=src:. python benchmarks/bench_runtime.py [--n 10000]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.decision import (
    DecisionBatch,
    DecisionEngine,
    LeastPredictedWaitBalancer,
    MinLatencyPolicy,
    PredictedEdgeQueue,
    RoundRobinBalancer,
)
from repro.core.fit import build_fleet_predictor, build_predictor, fit_app
from repro.core.records import RecordBatch
from repro.core.runtime import PlacementRuntime, TwinBackend
from repro.core.workload import BurstyWorkload
from benchmarks import common
from benchmarks.common import banner

CONFIGS = (1280, 1536, 1792, 2048)
C_MAX, ALPHA = 2.97e-5, 0.02

# the fleet scenario: two full-speed devices + one slower straggler
FLEET_SPEEDS = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}
FLEET_NAMES = tuple(FLEET_SPEEDS)
FLEET_C_MAX = 2e-6  # edge-first budget: bursts must be absorbed by the fleet


def _bursty(twin, n: int, rate_per_s: float = 4.0, seed: int = 7):
    return BurstyWorkload(rate_per_s=rate_per_s, size_sampler=twin.sample_input,
                          burst_multiplier=6.0, mean_quiet_s=15.0,
                          mean_burst_s=6.0, seed=seed).generate(n)


def _fleet_engine(models, c_max=0.0, alpha=0.0, columnar=True, **kwargs):
    pred = build_fleet_predictor(models, dict(FLEET_SPEEDS), configs=CONFIGS)
    return DecisionEngine(predictor=pred,
                          policy=MinLatencyPolicy(c_max=c_max, alpha=alpha),
                          columnar=columnar, **kwargs)


def _warm_model_caches(models, tasks):
    """Build the per-(model, memory) GBRT step tables once so best-of-reps
    timing measures the steady state, not one-time cache construction."""
    build_fleet_predictor(models, dict(FLEET_SPEEDS),
                          configs=CONFIGS).predict_batch(tasks[:64])


# ------------------------------------------------- 1. the columnar decisions
def _decision_case(emit, models, tasks, label, c_max, alpha, min_speedup,
                   step_n: int, reps: int = 3):
    n = len(tasks)
    col_s = walk_s = float("inf")
    col = walk = None
    stats = None
    for _ in range(reps):
        eng = _fleet_engine(models, c_max, alpha, columnar=True)
        t0 = time.perf_counter()
        col = eng.place_many(tasks)
        col_s = min(col_s, time.perf_counter() - t0)
        stats = eng.columnar_stats

        eng = _fleet_engine(models, c_max, alpha, columnar=False)
        t0 = time.perf_counter()
        walk = eng.place_many(tasks)
        walk_s = min(walk_s, time.perf_counter() - t0)

    # per-task place() loop, timed on a prefix (it is ~two orders slower)
    eng_step = _fleet_engine(models, c_max, alpha)
    queues = {nm: PredictedEdgeQueue() for nm in FLEET_NAMES}
    sub = tasks[:step_n]
    t0 = time.perf_counter()
    step = []
    for t in sub:
        waits = {nm: q.wait_ms(t.arrival_ms) for nm, q in queues.items()}
        d = eng_step.place(t, t.arrival_ms, edge_waits=waits)
        if d.target in queues:
            queues[d.target].push(t.arrival_ms, d.prediction.comp_ms)
        step.append(d)
    step_s = (time.perf_counter() - t0) / max(len(sub), 1) * n

    assert isinstance(col, DecisionBatch), "columnar path did not engage"
    col_targets = col.target_list()
    assert col_targets == [d.target for d in walk], \
        f"{label}: columnar decisions diverged from the walk"
    assert col_targets[:len(step)] == [d.target for d in step], \
        f"{label}: columnar decisions diverged from the step loop"
    vs_walk = walk_s / max(col_s, 1e-12)
    vs_step = step_s / max(col_s, 1e-12)
    print(f"{label:<16} columnar {n / col_s:>10,.0f} t/s  "
          f"walk {n / walk_s:>8,.0f} t/s  step {n / step_s:>7,.0f} t/s  "
          f"vs-walk {vs_walk:5.1f}x  vs-step {vs_step:6.1f}x  "
          f"repairs {stats['repairs']}  walked {stats['walked']}")
    assert vs_walk >= min_speedup, \
        f"{label}: expected >={min_speedup}x vs walk, got {vs_walk:.1f}x"
    emit(f"runtime/place_many_columnar[{label}]", col_s / n * 1e6,
         f"n={n};speedup={vs_walk:.1f}x;vs_step={vs_step:.1f}x")
    emit(f"runtime/place_many_walk[{label}]", walk_s / n * 1e6, f"n={n}")
    emit(f"runtime/place_step[{label}]", step_s / n * 1e6, f"n={n}")
    return vs_walk


def run_decision(emit, n: int | None = None, min_speedup: float = 10.0,
                 mixed_min_speedup: float = 1.5):
    if n is None:
        n = 20_000 if common.REDUCED else 100_000
    banner(f"bench_runtime/decision — columnar place_many vs walk vs step "
           f"({n} tasks, 3-device fleet)")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)
    _warm_model_caches(models, tasks)
    step_n = min(n, 4_000 if common.REDUCED else 10_000)

    # saturated fleet: every decision lands on a device — zero repairs, the
    # speculate-and-repair fast path at full speed (the acceptance bar)
    _decision_case(emit, models, tasks, "fleet-saturated", 0.0, 0.0,
                   min_speedup, step_n)
    # mixed budget: edge/cloud oscillation forces repair segments; the
    # columnar core must still win, with a softer bar (fixed segment-pass
    # overheads only amortize at scale, so tiny --n runs just must not lose)
    _decision_case(emit, models, tasks, "mixed-cloud", 2e-5, 0.0,
                   mixed_min_speedup if n >= 50_000 else min(
                       mixed_min_speedup, 1.0), step_n)


# --------------------------------------------------- 2. end-to-end serve
def _serve_case(emit, twin, models, tasks, label, c_max, alpha, min_speedup,
                reps: int = 3):
    n = len(tasks)

    def runtime(columnar):
        eng = _fleet_engine(models, c_max, alpha, columnar=columnar)
        backend = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                              edge_speed=FLEET_SPEEDS)
        return PlacementRuntime(eng, backend)

    col_s = obj_s = float("inf")
    res_col = res_obj = None
    for _ in range(reps):
        rt = runtime(True)
        t0 = time.perf_counter()
        res_col = rt.serve(tasks)
        col_s = min(col_s, time.perf_counter() - t0)
        rt = runtime(False)
        t0 = time.perf_counter()
        res_obj = rt.serve(tasks)
        obj_s = min(obj_s, time.perf_counter() - t0)

    assert isinstance(res_col.records, RecordBatch)
    identical = (res_col.total_actual_cost == res_obj.total_actual_cost
                 and res_col.avg_actual_latency_ms == res_obj.avg_actual_latency_ms
                 and bool((res_col.records.targets == res_obj.records.targets).all()))
    speedup = obj_s / max(col_s, 1e-12)
    print(f"{label:<16} array-native {n / col_s:>10,.0f} t/s  "
          f"objects {n / obj_s:>8,.0f} t/s  speedup {speedup:5.1f}x  "
          f"identical={identical}")
    assert identical, f"{label}: columnar serve diverged from the object path"
    assert speedup >= min_speedup, \
        f"{label}: expected >={min_speedup}x end-to-end, got {speedup:.1f}x"
    emit(f"runtime/serve_columnar[{label}]", col_s / n * 1e6,
         f"n={n};speedup={speedup:.1f}x")
    emit(f"runtime/serve_objects[{label}]", obj_s / n * 1e6, f"n={n}")


def run_serve(emit, n: int | None = None, min_speedup: float = 5.0,
              mixed_min_speedup: float = 1.5):
    if n is None:
        n = 20_000 if common.REDUCED else 100_000
    banner(f"bench_runtime/serve — array-native serve vs legacy object path "
           f"({n} tasks)")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)
    _warm_model_caches(models, tasks)

    # saturated fleet: the acceptance bar — every stage on arrays end-to-end
    _serve_case(emit, twin, models, tasks, "fleet-saturated", 0.0, 0.0,
                min_speedup)
    # edge-first budget: periodic cloud offloads force dense repair segments;
    # the array path must still win, with a softer bar (tiny --n runs just
    # must not lose — fixed pass overheads only amortize at scale)
    _serve_case(emit, twin, models, tasks, "edge-budget", FLEET_C_MAX, 0.01,
                mixed_min_speedup if n >= 50_000 else min(
                    mixed_min_speedup, 1.0))


# ----------------------------------------------------- 2. twin execution
def _twin_exec_case(emit, twin, tasks, targets, label: str, min_speedup: float,
                    reps: int = 3):
    """Best-of-``reps`` wall time per path (standard microbenchmark
    de-noising — each rep uses a fresh backend, so every run does identical
    work from identical state)."""
    n = len(tasks)
    seq_s = vec_s = float("inf")
    outs_seq = batch = None
    for _ in range(reps):
        b_seq = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                            edge_speed=FLEET_SPEEDS)
        t0 = time.perf_counter()
        outs_seq = [b_seq.execute(t, tg, t.arrival_ms)
                    for t, tg in zip(tasks, targets)]
        seq_s = min(seq_s, time.perf_counter() - t0)

        b_vec = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                            edge_speed=FLEET_SPEEDS)
        t0 = time.perf_counter()
        batch = b_vec.execute_many(tasks, targets)
        vec_s = min(vec_s, time.perf_counter() - t0)

    identical = outs_seq == batch.outcomes()
    speedup = seq_s / max(vec_s, 1e-12)
    edge_pct = 100.0 * sum(1 for tg in targets if tg in FLEET_SPEEDS) / n
    print(f"{label:<18} edge {edge_pct:5.1f}%  "
          f"seq {n / seq_s:>9.0f} t/s  vec {n / vec_s:>10.0f} t/s  "
          f"speedup {speedup:5.1f}x  identical={identical}")
    assert identical, f"{label}: vectorized outcomes diverged from execute()"
    assert speedup >= min_speedup, \
        f"{label}: expected >={min_speedup}x, got {speedup:.1f}x"
    emit(f"runtime/execute_seq[{label}]", seq_s / n * 1e6, f"n={n}")
    emit(f"runtime/execute_many[{label}]", vec_s / n * 1e6,
         f"n={n};speedup={speedup:.1f}x")
    return speedup


def run_twin_exec(emit, n: int | None = None, min_speedup: float = 10.0,
                  mixed_min_speedup: float = 3.0):
    if n is None:
        n = 20_000 if common.REDUCED else 100_000
    banner(f"bench_runtime/twin-exec — execute_many vs execute loop ({n} tasks)")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)

    def targets_for(c_max):
        eng = DecisionEngine(
            predictor=build_fleet_predictor(models, FLEET_SPEEDS, configs=CONFIGS),
            policy=MinLatencyPolicy(c_max=c_max, alpha=0.01))
        return [d.target for d in eng.place_many(tasks)]

    # saturated fleet: the budget keeps the whole burst load on the devices —
    # the regime the vectorized sampler exists for (and the acceptance bar)
    _twin_exec_case(emit, twin, tasks, targets_for(0.0),
                    "fleet-saturated", min_speedup)
    # mixed split: the cloud container-pool walk is sequential bookkeeping,
    # so the ratio is structurally lower — reported with a soft sanity bar
    _twin_exec_case(emit, twin, tasks, targets_for(2e-5), "mixed-cloud",
                    mixed_min_speedup)


# ------------------------------------------------------------- 3. the fleet
def _fleet_runtime(twin, models, balancer=None, devices=None):
    devices = devices if devices is not None else dict(FLEET_SPEEDS)
    pred = build_fleet_predictor(models, devices, configs=CONFIGS)
    kwargs = {"balancer": balancer} if balancer is not None else {}
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=FLEET_C_MAX, alpha=ALPHA),
                         **kwargs)
    backend = TwinBackend(twin, seed=11, edge_names=tuple(devices),
                          edge_speed=devices)
    return PlacementRuntime(eng, backend)


def _single_runtime(twin, models):
    pred = build_predictor(models, configs=CONFIGS)
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=FLEET_C_MAX, alpha=ALPHA))
    return PlacementRuntime(eng, TwinBackend(twin, seed=11))


def run_fleet(emit, n: int | None = None):
    if n is None:
        n = 1_500 if common.REDUCED else 4_000
    banner(f"bench_runtime/fleet — 3-device fleet vs single edge, "
           f"skewed arrivals ({n} tasks)")
    twin, models = fit_app("IR", seed=0, n_inputs=150, configs=CONFIGS)
    tasks = _bursty(twin, n)

    lpw = _fleet_runtime(twin, models, LeastPredictedWaitBalancer()).serve(tasks)
    rr = _fleet_runtime(twin, models, RoundRobinBalancer()).serve(tasks)
    single = _single_runtime(twin, models).serve(tasks)

    rows = [("fleet-3 least-wait", lpw), ("fleet-3 round-robin", rr),
            ("single edge", single)]
    print(f"{'configuration':<22} {'mean ms':>9} {'p99 ms':>10} {'edge#':>6}")
    for name, res in rows:
        print(f"{name:<22} {res.avg_actual_latency_ms:>9.0f} "
              f"{res.p99_actual_latency_ms:>10.0f} {res.n_edge:>6d}")
    print("\nleast-wait fleet balance:")
    print(lpw.device_table())

    assert lpw.avg_actual_latency_ms < single.avg_actual_latency_ms, \
        "fleet must beat the single-edge configuration on mean latency"
    assert lpw.avg_actual_latency_ms < rr.avg_actual_latency_ms, \
        "least-predicted-wait must beat round-robin on skewed arrivals"
    emit("runtime/fleet_lpw_mean_us", lpw.avg_actual_latency_ms * 1e3, f"n={n}")
    emit("runtime/fleet_rr_mean_us", rr.avg_actual_latency_ms * 1e3, f"n={n}")
    emit("runtime/single_edge_mean_us", single.avg_actual_latency_ms * 1e3,
         f"n={n}")


# --------------------------------------------- 5. live async overlap (ISSUE 4)
def run_live_async(emit, n: int | None = None, min_speedup: float = 2.0):
    """Wall-clock overlap of the live event-driven driver vs sequential
    dispatch: a saturated 3-device edge fleet (edge-only budget) serving real
    compiled executions whose store leg pays an emulated WAN result-upload
    (real ``time.sleep`` waits — the paper's IoT-upload leg). The async
    driver's per-device workers overlap those waits and the compute; the
    sequential driver pays them back-to-back. Placement is identical on both
    sides, so the ratio is pure execution overlap.
    """
    if n is None:
        n = 60 if common.REDUCED else 120
    banner(f"bench_runtime/async-overlap — live serve_async vs sequential "
           f"({n} tasks, 3-device fleet, WAN-emulated store leg)")
    import os

    if (os.cpu_count() or 1) < 2:
        # single core: compute cannot overlap at all, only the WAN waits can
        # — the 2x acceptance bar is judged on >=2 unthrottled cores
        min_speedup = min(min_speedup, 1.2)

    from repro.configs import smoke_config
    from repro.serving.executors import NetworkProfile, SliceSpec
    from repro.serving.placement import (
        calibrate_catalog,
        llm_workload,
        make_live_runtime,
    )

    cfg = smoke_config("llama3.2-1b").with_updates(
        n_layers=2, d_model=32, d_ff=64, vocab=64, n_heads=2, n_kv_heads=2,
        head_dim=16)
    specs = [SliceSpec("s2", 2, tokens_per_step=4),
             SliceSpec("s8", 8, tokens_per_step=4)]
    t0 = time.perf_counter()
    cat = calibrate_catalog(cfg, specs, n_tasks=6, n_cold=1, seed=0,
                            mean_tokens=16.0)
    calib_s = time.perf_counter() - t0
    # arrivals far above fleet capacity: predicted queues build up, so the
    # least-wait balancer spreads the backlog evenly over all three devices
    tasks = llm_workload(n, rate_per_s=2_000.0, seed=4, mean_tokens=16.0)
    net = NetworkProfile(base_ms=40.0, ms_per_byte=0.01)

    def runtime():
        # c_max=0: every task is edge-feasible only — the saturated fleet
        return make_live_runtime(cat, MinLatencyPolicy(c_max=0.0, alpha=0.0),
                                 t_idl_ms=60_000.0, n_edge_devices=3,
                                 network=net)

    rt_seq = runtime()
    t0 = time.perf_counter()
    res_seq = rt_seq.serve(tasks)
    seq_s = time.perf_counter() - t0

    rt_async = runtime()
    t0 = time.perf_counter()
    res_async = rt_async.serve_async(tasks)
    async_s = time.perf_counter() - t0

    assert res_seq.n == n and res_async.n == n
    assert res_async.n_edge == n, "budget must saturate the edge fleet"
    assert [r.target for r in res_seq.records] \
        == [r.target for r in res_async.records], "placement must be identical"
    speedup = seq_s / max(async_s, 1e-12)
    print(f"calibration {calib_s:5.1f}s   sequential {seq_s:6.2f}s "
          f"({n / seq_s:5.1f} t/s)   async {async_s:6.2f}s "
          f"({n / async_s:5.1f} t/s)   overlap speedup {speedup:4.2f}x   "
          f"cores {os.cpu_count()}")
    print("async fleet balance:")
    print(res_async.device_table())
    assert speedup >= min_speedup, \
        f"live async overlap: expected >={min_speedup}x, got {speedup:.2f}x"
    emit("runtime/live_serve_async[fleet-wan]", async_s / n * 1e6,
         f"n={n};speedup={speedup:.2f}x")
    emit("runtime/live_serve_seq[fleet-wan]", seq_s / n * 1e6, f"n={n}")


# ------------------------------------------------------- 6. the 1M scenario
def run_million(emit, n: int = 1_000_000):
    """The columnar end-to-end scale-out: 1M tasks through decisions AND
    execution without a single per-task Python object on the hot path.
    Previously impractical — the object walk alone took minutes and built
    millions of Prediction/Decision/Record objects."""
    banner(f"bench_runtime/million — columnar serve at {n:,} tasks")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    t0 = time.perf_counter()
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)
    gen_s = time.perf_counter() - t0
    _warm_model_caches(models, tasks)

    eng = _fleet_engine(models, FLEET_C_MAX, 0.01, columnar=True)
    backend = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                          edge_speed=FLEET_SPEEDS)
    rt = PlacementRuntime(eng, backend)
    t0 = time.perf_counter()
    res = rt.serve(tasks)
    serve_s = time.perf_counter() - t0

    assert res.n == n and isinstance(res.records, RecordBatch)
    assert res.n_edge > 0
    print(f"workload gen {gen_s:6.1f}s   serve {serve_s:6.1f}s "
          f"({n / serve_s:,.0f} tasks/s)   "
          f"decision stats {eng.columnar_stats}")
    print(f"mean latency {res.avg_actual_latency_ms:,.0f} ms   "
          f"p99 {res.p99_actual_latency_ms:,.0f} ms   edge {res.n_edge:,}/{n:,}")
    emit("runtime/serve_1m", serve_s / n * 1e6,
         f"n={n};tasks_per_s={n / serve_s:.0f}")


# --------------------------------------- 7. streaming scale (ISSUE 5)
def _stream_runtime(twin, models, c_max=0.0):
    eng = _fleet_engine(models, c_max, 0.0, columnar=True)
    backend = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                          edge_speed=FLEET_SPEEDS)
    return PlacementRuntime(eng, backend)


def run_streaming(emit, n: int = 10_000_000, n_oneshot: int = 1_000_000,
                  chunk: int = 262_144, min_rel_rate: float = 1.0,
                  smoke: bool = False):
    """``serve_stream`` at scale: constant working set, one-shot throughput.

    Full: 10M tasks streamed as ``TaskChunk``s (vectorized Poisson/STT
    generation — no per-task objects anywhere), ``keep_tasks=False``; the
    peak-RSS delta over the pre-stream baseline must stay under the result
    arena's own footprint plus a fixed working-set allowance — i.e. nowhere
    near the one-shot path's O(n × targets) matrices. Throughput must be ≥
    ``min_rel_rate`` × the one-shot ``serve(batched=True)`` rate measured on
    an ``n_oneshot`` list in the same process (the PR 3 acceptance regime:
    saturated fleet, every decision on a device). Smoke: small n, tracemalloc
    ceiling, relaxed rate floor.
    """
    import resource

    banner(f"bench_runtime/streaming-scale — serve_stream at {n:,} tasks "
           f"(chunk {chunk:,})")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    wl = twin.poisson(seed=3)
    # warm model caches + first-touch allocations outside the measured window
    _stream_runtime(twin, models).serve_stream(
        wl.chunks(min(chunk, 65_536), 65_536), chunk_size=chunk,
        keep_tasks=False)

    # the arena's exact per-row footprint, derived from its column spec so
    # the ceiling formula can never silently drift from the implementation
    from repro.core import records as records_mod

    arena_row_bytes = (8 * (len(records_mod._ARENA_F64) + 1)    # + arrivals
                       + 8 * (len(records_mod._ARENA_I64) + 1)  # + task_idx
                       + len(records_mod._ARENA_BOOL))
    if smoke:
        import tracemalloc

        rt = _stream_runtime(twin, models)
        t0 = time.perf_counter()
        res = rt.serve_stream(wl.chunks(n, chunk), chunk_size=chunk,
                              keep_tasks=False, expected_tasks=n)
        stream_s = time.perf_counter() - t0
        # memory pass: tracemalloc taxes allocation, so rate is timed above
        tracemalloc.start()
        _stream_runtime(twin, models).serve_stream(
            twin.poisson(seed=4).chunks(n, chunk), chunk_size=chunk,
            keep_tasks=False, expected_tasks=n)
        peak_mb = tracemalloc.get_traced_memory()[1] / 1e6
        tracemalloc.stop()
        ceiling_mb = n * arena_row_bytes / 1e6 * 1.6 + 250.0
        mem_label = f"tracemalloc peak {peak_mb:.0f} MB (ceiling {ceiling_mb:.0f})"
    else:
        rss0_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
        rt = _stream_runtime(twin, models)
        t0 = time.perf_counter()
        res = rt.serve_stream(wl.chunks(n, chunk), chunk_size=chunk,
                              keep_tasks=False, expected_tasks=n)
        stream_s = time.perf_counter() - t0
        peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
        ceiling_mb = rss0_mb + n * arena_row_bytes / 1e6 * 1.25 + 700.0
        mem_label = (f"peak RSS {peak_mb:.0f} MB "
                     f"(baseline {rss0_mb:.0f}, ceiling {ceiling_mb:.0f})")
    assert res.n == n and res.n_edge == n, "budget must saturate the fleet"
    assert len(res.records.tasks) == 0  # constant-memory result
    rate_stream = n / stream_s

    # one-shot baseline AFTER the stream so its (bigger) footprint cannot
    # pollute the streaming RSS window
    tasks = wl.generate(n_oneshot)
    rt1 = _stream_runtime(twin, models)
    t0 = time.perf_counter()
    res1 = rt1.serve(tasks, batched=True)
    one_s = time.perf_counter() - t0
    assert res1.n == n_oneshot
    rate_one = n_oneshot / one_s
    rel = rate_stream / rate_one

    print(f"stream {n:,} in {stream_s:6.1f}s  ({rate_stream:,.0f} t/s)  "
          f"{mem_label}")
    print(f"one-shot {n_oneshot:,} in {one_s:6.1f}s  ({rate_one:,.0f} t/s)  "
          f"stream/one-shot rate {rel:4.2f}x   "
          f"stream stats {rt.stream_stats}")
    assert peak_mb <= ceiling_mb, \
        f"streaming memory ceiling exceeded: {peak_mb:.0f} > {ceiling_mb:.0f} MB"
    assert rel >= min_rel_rate, \
        f"streaming must serve at >={min_rel_rate}x the one-shot rate, got {rel:.2f}x"
    emit(f"runtime/serve_stream[{n}]", stream_s / n * 1e6,
         f"n={n};chunk={chunk};speedup={rel:.2f}x;peak_mb={peak_mb:.0f}")
    emit(f"runtime/serve_oneshot[{n_oneshot}]", one_s / n_oneshot * 1e6,
         f"n={n_oneshot}")


# module-level shard context so process-mode factories pickle by name.
# Forked children inherit the parent's fitted models for free; spawn-based
# platforms (macOS/Windows default) re-import this module with an empty dict,
# so the accessor lazily re-fits in the child rather than KeyError-ing.
_SHARD_CTX: dict = {}


def _shard_setup(app):
    if app not in _SHARD_CTX:
        _SHARD_CTX[app] = fit_app(app, seed=0, n_inputs=120, configs=CONFIGS)
    return _SHARD_CTX[app]


def _sharded_runtime(app):
    twin, models = _shard_setup(app)
    pred = build_fleet_predictor(models, dict(FLEET_SPEEDS), configs=CONFIGS)
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=0.0, alpha=0.0))
    return PlacementRuntime(eng, TwinBackend(
        twin, seed=7, edge_names=FLEET_NAMES, edge_speed=FLEET_SPEEDS))


def _sharded_workload(app, n, chunk):
    return _shard_setup(app)[0].poisson(seed=3).chunks(n, chunk)


def run_sharded(emit, n_per_app: int = 500_000, chunk: int = 65_536,
                min_speedup: float = 2.0):
    """``serve_sharded``: the EdgeBench-style IR+FD+STT mix as parallel
    shards — each with its own Predictor, budget, and fleet partition.

    Per-record parity across sequential / thread / process modes is the hard
    assertion (shards share no state, so scheduling must not perturb one
    draw). The ≥2x wall-clock floor over sequential per-app serves is
    asserted on ≥ 4 cores; CPU-bound shards cannot physically beat ~1x on
    the 2-core class (measured and reported, never asserted there).
    """
    import functools
    import os

    from repro.core.multiapp import AppShard, ShardedRuntime

    apps = ("IR", "FD", "STT")
    banner(f"bench_runtime/sharded — {'+'.join(apps)} parallel shards "
           f"({n_per_app:,} tasks/app)")
    for app in apps:
        _shard_setup(app)

    def shards():
        return [AppShard(name=app,
                         runtime=functools.partial(_sharded_runtime, app),
                         workload=functools.partial(_sharded_workload, app,
                                                    n_per_app, chunk),
                         chunk_size=chunk)
                for app in apps]

    # warm EVERY shard's one-time caches (GBRT step tables are process-wide
    # and fork-inherited, so leaving FD/STT cold would bill their derivation
    # to the sequential baseline only and inflate the measured speedup)
    warm = [AppShard(name=app,
                     runtime=functools.partial(_sharded_runtime, app),
                     workload=functools.partial(_sharded_workload, app,
                                                4_096, chunk),
                     chunk_size=chunk)
            for app in apps]
    ShardedRuntime(warm).serve(parallel=False)
    seq = ShardedRuntime(shards()).serve(parallel=False)
    thr = ShardedRuntime(shards()).serve(parallel=True)
    proc = ShardedRuntime(shards()).serve(parallel=True, use_processes=True)

    for app in apps:
        a, b, c = (m.results[app].records for m in (seq, thr, proc))
        assert np.array_equal(a.actual_latency_ms, b.actual_latency_ms) \
            and np.array_equal(a.actual_latency_ms, c.actual_latency_ms) \
            and a.target_codes.tolist() == b.target_codes.tolist() \
            == c.target_codes.tolist(), \
            f"{app}: sharded results diverged across execution modes"

    thr_x = seq.elapsed_s / max(thr.elapsed_s, 1e-9)
    proc_x = seq.elapsed_s / max(proc.elapsed_s, 1e-9)
    cores = os.cpu_count() or 1
    print(f"sequential {seq.elapsed_s:6.2f}s   threads {thr.elapsed_s:6.2f}s "
          f"({thr_x:4.2f}x)   processes {proc.elapsed_s:6.2f}s "
          f"({proc_x:4.2f}x)   cores {cores}")
    print(thr.table())
    best = max(thr_x, proc_x)
    if cores >= 4:
        assert best >= min_speedup, \
            f"sharded overlap: expected >={min_speedup}x on {cores} cores, " \
            f"got {best:.2f}x"
    else:
        print(f"(floor not asserted: {cores} cores cannot overlap 3 "
              f"CPU-bound shards — parity checks above are the gate)")
    emit("runtime/sharded_thread[3app]", thr.elapsed_s / (3 * n_per_app) * 1e6,
         f"n={3 * n_per_app};speedup={thr_x:.2f}x;cores={cores}")
    emit("runtime/sharded_process[3app]",
         proc.elapsed_s / (3 * n_per_app) * 1e6,
         f"n={3 * n_per_app};speedup={proc_x:.2f}x;cores={cores}")
    emit("runtime/sharded_seq[3app]", seq.elapsed_s / (3 * n_per_app) * 1e6,
         f"n={3 * n_per_app}")


# --------------------------- 8. trace replay + capacity planner (ISSUE 6)
def _record_trace(wl, n: int, chunk: int, app: str):
    """Record a workload's chunk stream into a ``Trace`` (columns only —
    the bench never materializes per-task objects)."""
    from repro.trace import Trace

    cols = ([], [], [])
    for c in wl.chunks(n, chunk):
        cols[0].append(c.arrival_ms)
        cols[1].append(c.size)
        cols[2].append(c.bytes)
    return Trace.from_arrays(*(np.concatenate(x) for x in cols),
                             app_names=(app,))


def run_trace_planner(emit, n: int = 50_000, chunk: int = 16_384,
                      max_rel: float = 1.2, smoke: bool = False):
    """Trace replay rate + what-if planner search (ISSUE 6).

    Replay floor: streaming a recorded trace through ``serve_stream``
    (``TraceWorkload`` chunk views) must land within ``max_rel``× the wall
    time of the equivalent in-memory stream (the workload generating the
    same chunks on the fly) — replay slices arrays instead of sampling, so
    it has no excuse to be slower; per-record parity between the two runs is
    asserted. Planner: an 8-candidate successive-halving search (fleet sizes
    1–4 × edge-only/cloud-budget policies) over the same trace; the winner
    must meet the SLO, be the cheapest config that does, and be verified on
    the full trace.
    """
    from repro.planner import Candidate, Planner, PolicySpec, SLO
    from repro.trace import TraceWorkload

    banner(f"bench_runtime/trace-planner — replay + what-if search "
           f"({n:,}-task STT trace)")
    twin, models = _shard_setup("STT")
    wl = twin.poisson(seed=3)
    trace = _record_trace(wl, n, chunk, "STT")
    reps = 1 if smoke else 2

    # warm caches outside the measured window
    _stream_runtime(twin, models).serve_stream(wl.chunks(4_096, chunk),
                                               chunk_size=chunk)
    mem_s = rep_s = float("inf")
    res_mem = res_rep = None
    for _ in range(reps):
        rt = _stream_runtime(twin, models)
        t0 = time.perf_counter()
        res_mem = rt.serve_stream(wl.chunks(n, chunk), chunk_size=chunk)
        mem_s = min(mem_s, time.perf_counter() - t0)

        rt = _stream_runtime(twin, models)
        t0 = time.perf_counter()
        res_rep = rt.serve_stream(TraceWorkload(trace).chunks(chunk_size=chunk),
                                  chunk_size=chunk)
        rep_s = min(rep_s, time.perf_counter() - t0)

    a, b = res_mem.records, res_rep.records
    identical = (a.target_codes.tolist() == b.target_codes.tolist()
                 and np.array_equal(a.actual_latency_ms, b.actual_latency_ms)
                 and np.array_equal(a.actual_cost, b.actual_cost))
    rel = rep_s / max(mem_s, 1e-12)
    print(f"in-memory {n / mem_s:>9,.0f} t/s   replay {n / rep_s:>9,.0f} t/s "
          f"  rel {rel:4.2f}x (floor {max_rel:.1f}x)   identical={identical}")
    assert identical, "trace replay diverged from the in-memory stream"
    assert rel <= max_rel, \
        f"trace replay {rel:.2f}x slower than in-memory (floor {max_rel}x)"
    emit(f"trace/replay_stream[{n}]", rep_s / n * 1e6,
         f"n={n};chunk={chunk};speedup={mem_s / max(rep_s, 1e-12):.2f}x")

    # ---- the 8-candidate what-if search
    edge_only = PolicySpec(kind="min_latency", c_max=0.0)
    mixed = PolicySpec(kind="min_latency", c_max=C_MAX, alpha=ALPHA)
    cands = [Candidate.make(f"fleet-{k}-{tag}", k, policy=pol,
                            cloud_configs=CONFIGS, chunk_size=chunk,
                            device_rate_per_hour=0.05)
             for k in (1, 2, 3, 4)
             for tag, pol in (("edge", edge_only), ("mixed", mixed))]
    slo = SLO(latency_ms=40_000.0, target=0.95)
    planner = Planner(trace, slo, fit_seed=0, n_inputs=120,
                      fit_configs=CONFIGS)
    t0 = time.perf_counter()
    res = planner.plan(cands, strategy="halving", rungs=3, min_rung_n=2_048)
    plan_s = time.perf_counter() - t0

    print(res.table())
    print(f"planner: {len(cands)} candidates, {res.replayed_tasks:,} tasks "
          f"replayed ({res.mode}) in {plan_s:.1f}s   best "
          f"{res.best.candidate.name}")
    assert res.best.meets_slo, "no candidate met the SLO on the bench fixture"
    assert res.best.n == trace.n, "winner must be verified on the full trace"
    meeting = [s for s in res.scores if s.meets_slo]
    assert res.best.total_cost == min(s.total_cost for s in meeting), \
        "planner returned a non-cheapest SLO-meeting candidate"
    emit(f"trace/planner_search[{len(cands)}cand]",
         plan_s / max(res.replayed_tasks, 1) * 1e6,
         f"n={res.replayed_tasks};candidates={len(cands)};"
         f"best={res.best.candidate.name}")


# --------------------------------------------- 9. device core (ISSUE 7)
def run_jax_core(emit, n: int = 1_000_000, chunk: int = 65_536,
                 min_speedup: float = 2.0, smoke: bool = False):
    """Device-resident predict→place (ISSUE 7): jax core vs numpy oracle.

    Full: a steady Poisson STT stream (containers stay warm — the container
    pool and the fixed-point pass count sit at their steady state) served
    end-to-end with ``array_backend="jax"`` vs ``"numpy"``; decisions must be
    identical, and on an accelerator the device core must clear
    ``min_speedup``× the numpy rate (report-only on CPU, where XLA's
    sequential scans lose to numpy's cumsum segments — the same trace is the
    fast path on TPU). Smoke: bit-parity of ``"jax_interpret"`` against the
    oracle per record, decision-equality of compiled ``"jax"``, and the
    no-retrace gate — a second same-shape stream must reuse every jit cache
    entry after the warmup serve.

    Both variants finish with the SCAN_MODE audit: "seq" and "assoc" are
    timed on compiled streams (warmup + compile-free rerun each) and the
    winner is compared to what ``resolve_scan_mode`` picks for this backend
    under ``SCAN_MODE="auto"`` — asserted at full size on accelerators,
    report-only on CPU (timing noise at smoke sizes makes the "winner" a
    coin flip there; the table itself was derived at full size).
    """
    import jax as jax_mod

    from repro.core import jax_core

    backend_name = jax_mod.default_backend()
    on_accel = backend_name != "cpu"
    banner(f"bench_runtime/jax-core — device-resident placement at {n:,} "
           f"tasks (chunk {chunk:,}, backend {backend_name})")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)

    def _serve(backend, n_tasks, seed=3):
        rt = _stream_runtime(twin, models, c_max=FLEET_C_MAX)
        src = twin.poisson(seed=seed)
        t0 = time.perf_counter()
        res = rt.serve_stream(src.chunks(n_tasks, chunk), chunk_size=chunk,
                              array_backend=backend)
        return res, time.perf_counter() - t0, rt

    if smoke:
        n = min(n, 3_000)
        # ---- parity gate: interpret vs oracle, bit-identical per record
        ref, _, _ = _serve("numpy", n)
        it, _, rt_it = _serve("jax_interpret", n)
        cols = ("predicted_latency_ms", "predicted_cost", "actual_latency_ms",
                "actual_cost", "allowed_cost", "completion_ms",
                "queue_wait_ms", "predicted_cold", "actual_cold", "feasible")
        bit_ok = (ref.records.target_codes.tolist()
                  == it.records.target_codes.tolist()
                  and all(np.array_equal(getattr(ref.records, c),
                                         getattr(it.records, c))
                          for c in cols))
        assert bit_ok, "jax_interpret diverged from the numpy oracle"
        assert rt_it.engine.jax_stats["interpret"]
        print(f"interpret parity  : {n:,} records bit-identical "
              f"(stats {rt_it.engine.jax_stats})")

    # first serve compiles and grows the container-pool cap to steady state;
    # the second stream reuses the SAME engine (and so the same jit caches):
    # same chunk shapes ⇒ it must not retrace, and its time is compile-free
    comp, jax_s, rt_jx = _serve("jax", n)
    core = jax_core.core_for(rt_jx.engine)
    stats_before = core.compile_stats()
    t0 = time.perf_counter()
    rt_jx.serve_stream(twin.poisson(seed=5).chunks(n, chunk),
                       chunk_size=chunk, array_backend="jax")
    jax2_s = time.perf_counter() - t0
    assert jax_core.core_for(rt_jx.engine) is core
    stats_after = core.compile_stats()
    assert stats_after == stats_before, \
        f"jax core retraced on a same-shape stream: " \
        f"{stats_before} -> {stats_after}"
    jax_s = min(jax_s, jax2_s)

    ref, np_s, _ = _serve("numpy", n)
    assert (ref.records.target_codes.tolist()
            == comp.records.target_codes.tolist()), \
        "compiled jax decisions diverged from the numpy oracle"
    speedup = np_s / max(jax_s, 1e-12)
    bar = f"(floor {min_speedup:.1f}x)" if on_accel else "(report-only on CPU)"
    print(f"numpy {n / np_s:>9,.0f} t/s   jax[{backend_name}] "
          f"{n / jax_s:>9,.0f} t/s   speedup {speedup:4.2f}x {bar}   "
          f"no-retrace OK {stats_after}")
    if on_accel:
        assert speedup >= min_speedup, \
            f"device core {speedup:.2f}x below the {min_speedup}x floor " \
            f"on {backend_name}"
    emit(f"runtime/jax_core[{n}]", jax_s / n * 1e6,
         f"n={n};chunk={chunk};backend={backend_name};"
         f"speedup={speedup:.2f}x;accel={int(on_accel)}")

    # ---- SCAN_MODE audit (ISSUE 9): time the sequential lax.scan folds vs
    # the reassociated max-plus/cumsum forms and check the "auto" table
    # against the measurement. SCAN_MODE is part of the engine key, so each
    # mode gets its own core: warm it up, then time a compile-free rerun on
    # the SAME runtime (the same jit caches).
    n_scan = n if smoke else max(chunk, n // 4)
    mode_s = {}
    prior = jax_core.SCAN_MODE
    try:
        for sm in ("seq", "assoc"):
            jax_core.SCAN_MODE = sm
            rt_m = _stream_runtime(twin, models, c_max=FLEET_C_MAX)
            rt_m.serve_stream(twin.poisson(seed=3).chunks(n_scan, chunk),
                              chunk_size=chunk, array_backend="jax")
            t0 = time.perf_counter()
            rt_m.serve_stream(twin.poisson(seed=5).chunks(n_scan, chunk),
                              chunk_size=chunk, array_backend="jax")
            mode_s[sm] = time.perf_counter() - t0
    finally:
        jax_core.SCAN_MODE = prior
    winner = min(mode_s, key=mode_s.get)
    auto = jax_core.resolve_scan_mode(backend_name)
    gate = "asserted" if on_accel and not smoke else "report-only"
    print(f"scan-mode audit   seq {n_scan / mode_s['seq']:>9,.0f} t/s   "
          f"assoc {n_scan / mode_s['assoc']:>9,.0f} t/s   winner={winner}   "
          f"auto[{backend_name}]={auto} ({gate})")
    if on_accel and not smoke:
        assert auto == winner, \
            f"SCAN_MODE auto table picks {auto!r} on {backend_name} but " \
            f"the measurement favors {winner!r} — update jax_core._AUTO_SCAN"
    emit(f"runtime/scan_mode[{n_scan}]", mode_s[auto] / n_scan * 1e6,
         f"n={n_scan};seq_s={mode_s['seq']:.3f};"
         f"assoc_s={mode_s['assoc']:.3f};winner={winner};auto={auto};"
         f"backend={backend_name}")


# --------------------------------------------------- 10. chaos (ISSUE 8)
def run_chaos(emit, n: int | None = None, max_overhead: float = 0.03,
              min_top_slo: float = 0.99, smoke: bool = False, reps: int = 3):
    """Chaos twin (ISSUE 8): faults-off overhead floor + degradation smoke.

    Overhead: a runtime with retry + breaker + admission configured over an
    EMPTY ``FaultSpec`` must serve the saturated-fleet workload bit-identically
    per record to the plain runtime AND within ``max_overhead`` of its serve
    rate (the failure-aware round 0 issues the identical ``execute_many``
    call; everything else is gated fast paths). The 3% bar is judged at full
    size — smoke relaxes it (shared CI runners throttle) but keeps the parity
    gate at full strength. Degradation: one of the three devices down for the
    middle 30% of the run, 15% transient errors on one cloud config, with
    retry/failover/breaker/admission on — the top (non-sheddable) SLO tier
    must still make ``min_top_slo`` attainment, riding on failover and
    batch-tier shedding.
    """
    from repro.core.faults import (
        AdmissionPolicy,
        CircuitBreaker,
        FaultSpec,
        OutageWindow,
        RetryPolicy,
        SLOTier,
        TransientErrors,
    )

    if n is None:
        n = 20_000 if common.REDUCED else 100_000
    banner(f"bench_runtime/chaos — faults-off overhead + degradation "
           f"({n:,} tasks)")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)
    for t in tasks:
        t.tier = 0 if t.idx % 4 else 1      # 75% interactive, 25% batch
    _warm_model_caches(models, tasks)

    def runtime(faults=None, **knobs):
        eng = _fleet_engine(models, C_MAX, ALPHA)
        backend = TwinBackend(twin, seed=11, edge_names=FLEET_NAMES,
                              edge_speed=FLEET_SPEEDS, faults=faults)
        return PlacementRuntime(eng, backend, **knobs)

    # ---- faults-off overhead: empty spec + full failure machinery armed.
    # Stage-timed (placement and execution separately, best-of-reps each,
    # interleaved): the placement stage is identical code on both sides and
    # its run-to-run variance (CIL churn, GC) is several times the 3% bar,
    # so timing whole serves best-of-reps would measure noise, not the
    # failure-aware execute path this section gates.
    knobs = dict(retry=RetryPolicy(), breaker=CircuitBreaker(),
                 admission=AdmissionPolicy(tiers=(SLOTier(1e12),)))
    stage_s = {"plain": [float("inf")] * 2, "fa": [float("inf")] * 2}
    recs = {}
    for _ in range(reps):
        for tag, rt in (("plain", runtime()),
                        ("fa", runtime(faults=FaultSpec(), **knobs))):
            rt._snapshot_horizons()
            t0 = time.perf_counter()
            d = rt.engine.place_many(tasks, edge_queues=rt.edge_queues)
            stage_s[tag][0] = min(stage_s[tag][0], time.perf_counter() - t0)
            t0 = time.perf_counter()
            recs[tag] = rt._execute_decisions(tasks, d)
            stage_s[tag][1] = min(stage_s[tag][1], time.perf_counter() - t0)
    identical = all(
        np.array_equal(getattr(recs["plain"], c), getattr(recs["fa"], c))
        for c in ("actual_latency_ms", "actual_cost", "completion_ms",
                  "target_codes", "attempts"))
    plain_s, fa_s = (sum(stage_s[t]) for t in ("plain", "fa"))
    overhead = fa_s / max(plain_s, 1e-12) - 1.0
    print(f"faults-off        plain {n / plain_s:>10,.0f} t/s  "
          f"failure-aware {n / fa_s:>10,.0f} t/s  overhead {overhead:+6.1%}  "
          f"(exec stage {stage_s['plain'][1]:.3f}s -> "
          f"{stage_s['fa'][1]:.3f}s)  identical={identical}")
    assert identical, "empty FaultSpec diverged from the plain serve path"
    assert overhead <= max_overhead, \
        f"faults-off overhead {overhead:+.1%} above the " \
        f"{max_overhead:.0%} floor"
    emit(f"runtime/chaos_off[{n}]", fa_s / n * 1e6,
         f"n={n};overhead={overhead:+.3f}")

    # ---- degradation: edge1 down for the middle 30%, one flaky cloud config
    span = tasks[-1].arrival_ms
    top_slo_ms = 3.0 * float(np.percentile(
        recs["plain"].actual_latency_ms, 99))
    spec = FaultSpec(seed=7,
                     outages=[OutageWindow("edge1", 0.35 * span, 0.65 * span)],
                     transient=[TransientErrors("1792", 0.15)])
    rt = runtime(
        faults=spec, retry=RetryPolicy(max_attempts=4, backoff_ms=50.0),
        breaker=CircuitBreaker(threshold=3, probation_ms=30_000.0),
        admission=AdmissionPolicy(tiers=(
            SLOTier(top_slo_ms, sheddable=False),
            SLOTier(float(np.percentile(
                recs["plain"].actual_latency_ms, 50))))))
    t0 = time.perf_counter()
    res = rt.serve(tasks)
    chaos_s = time.perf_counter() - t0
    top = res.slo_attainment(top_slo_ms, tier=0)
    print(f"degraded (1/3 down 30%)  {n / chaos_s:>10,.0f} t/s  "
          f"top-tier SLO {top:6.2%} (floor {min_top_slo:.0%})  "
          f"retried {res.n_retried:,}  failed {res.n_failed:,}  "
          f"shed {res.n_shed:,}  breaker opens {rt.health.n_opens}")
    assert res.n_retried > 0, "the fault schedule never fired"
    assert top >= min_top_slo, \
        f"top-tier SLO attainment {top:.2%} under outage below the " \
        f"{min_top_slo:.0%} floor"
    emit(f"runtime/chaos_degraded[{n}]", chaos_s / n * 1e6,
         f"n={n};top_slo={top:.4f};retried={res.n_retried};"
         f"shed={res.n_shed};opens={rt.health.n_opens}")


# ----------------------------------------------- 11. residency (ISSUE 9)
def run_residency(emit, n: int = 1_000_000, chunk: int = 65_536,
                  min_rel_rate: float = 1.2, smoke: bool = False):
    """Persistent device residency (ISSUE 9): sync counts + resident rate.

    Steady stream: a Poisson STT stream served compiled (``"jax"``) with
    residency on keeps CIL pools / surplus bank / edge horizons device-side
    across chunks. The stream must place EVERY chunk resident — zero host
    commits at chunk boundaries, zero fallback syncs, at most the single
    stream-end materialization — while staying decision-identical to the
    PR 7 per-chunk path (``device_residency=False`` on an identical engine,
    which commits host state once per chunk). On an accelerator the resident
    stream must clear ``min_rel_rate``× the per-chunk rate (report-only on
    CPU, where the host commit is cheap relative to XLA's scan overhead).

    Fallback exits: a hedged chunk mid-stream is ineligible for the device
    core, so residency must exit through exactly ONE fallback sync (the host
    walk sees canonical state) and re-enter afterwards with state intact —
    the sync budget is per fallback EXIT, never per chunk.

    Smoke: the same counter + parity gates at small n; the rate floor is
    judged at full size on an accelerator only.
    """
    import jax as jax_mod

    from repro.core import jax_core
    from repro.core.decision import HedgedPolicy

    backend_name = jax_mod.default_backend()
    on_accel = backend_name != "cpu"
    if smoke:
        n = min(n, 3_000)
    banner(f"bench_runtime/residency — persistent device state at {n:,} "
           f"tasks (chunk {chunk:,}, backend {backend_name})")
    twin, models = fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)

    def _serve(rt, n_tasks, seed, **kw):
        if rt is None:
            rt = _stream_runtime(twin, models, c_max=FLEET_C_MAX)
        t0 = time.perf_counter()
        res = rt.serve_stream(twin.poisson(seed=seed).chunks(n_tasks, chunk),
                              chunk_size=chunk, array_backend="jax", **kw)
        return res, time.perf_counter() - t0, rt

    # ---- steady resident stream: the warmup serve compiles and grows the
    # container-pool cap to steady state; the rerun on the SAME engine is
    # compile-free and is what gets timed and counter-audited
    _, _, rt_res = _serve(None, n, 3)
    res_r, res_s, _ = _serve(rt_res, n, 5)
    chunks = rt_res.stream_stats["chunks"]
    r = rt_res.stream_stats["residency"]
    assert r["enabled"] and r["resident_chunks"] == chunks
    assert r["chunk_commits"] == 0, \
        "resident stream committed host state at a chunk boundary"
    assert r["fallback_syncs"] == 0, "steady stream took a fallback exit"
    assert r["state_syncs"] <= 1, \
        f"steady resident stream materialized {r['state_syncs']}x " \
        f"(budget: 1, the stream-end sync)"

    # ---- PR 7 per-chunk baseline: identical engine shape with residency
    # off — one host commit per chunk, decisions must not change
    _, _, rt_pc = _serve(None, n, 3, device_residency=False)
    res_p, pc_s, _ = _serve(rt_pc, n, 5, device_residency=False)
    rp = rt_pc.stream_stats["residency"]
    assert not rp["enabled"] and rp["chunk_commits"] == chunks
    assert (res_r.records.target_codes.tolist()
            == res_p.records.target_codes.tolist()), \
        "resident decisions diverged from the per-chunk path"
    rel = pc_s / max(res_s, 1e-12)
    bar = (f"(floor {min_rel_rate:.1f}x)" if on_accel and not smoke
           else "(report-only)")
    print(f"per-chunk {n / pc_s:>9,.0f} t/s   resident {n / res_s:>9,.0f} "
          f"t/s   rel {rel:4.2f}x {bar}   syncs/stream {r['state_syncs']}   "
          f"prefetched {r['prefetched']}")
    if on_accel and not smoke:
        assert rel >= min_rel_rate, \
            f"resident stream {rel:.2f}x below the {min_rel_rate}x floor " \
            f"on {backend_name}"

    # ---- fallback exits cost ONE sync each: chunk 2 of 4 runs under a
    # hedged policy (core-ineligible → host walk), chunks 0-1 and 3 stay
    # resident. Prefetch off: the transfer thread would fire the generator's
    # policy-swap side effect a chunk early.
    tasks = _bursty(twin, 2_000, rate_per_s=4.0, seed=7)

    def hedged_chunks(rt):
        orig = rt.engine.policy
        hedged = HedgedPolicy(MinLatencyPolicy(c_max=FLEET_C_MAX, alpha=0.0),
                              hedge_threshold_ms=50.0)
        for i in range(4):
            rt.engine.policy = hedged if i == 2 else orig
            yield tasks[i * 500:(i + 1) * 500]

    ref_rt = _stream_runtime(twin, models, c_max=FLEET_C_MAX)
    ref = ref_rt.serve_stream(hedged_chunks(ref_rt), chunk_size=500)
    rt_fb = _stream_runtime(twin, models, c_max=FLEET_C_MAX)
    res_fb = rt_fb.serve_stream(hedged_chunks(rt_fb), chunk_size=500,
                                array_backend="jax", prefetch=False)
    rf = rt_fb.stream_stats["residency"]
    assert (res_fb.records.target_codes.tolist()
            == ref.records.target_codes.tolist()), \
        "fallback/re-entry stream diverged from the numpy oracle"
    assert rf["fallback_syncs"] == 1, \
        f"one hedged chunk cost {rf['fallback_syncs']} fallback syncs"
    assert rf["state_syncs"] == 2     # the fallback exit + the stream end
    assert rf["resident_chunks"] == 3 and rf["chunk_commits"] == 0
    print(f"fallback exit     1 hedged chunk of 4 -> "
          f"{rf['fallback_syncs']} fallback sync / {rf['state_syncs']} total"
          f"   residency re-entered ({rf['resident_chunks']}/4 resident)")
    emit(f"runtime/residency[{n}]", res_s / n * 1e6,
         f"n={n};chunk={chunk};backend={backend_name};rel_rate={rel:.2f}x;"
         f"state_syncs={r['state_syncs']};prefetched={r['prefetched']};"
         f"accel={int(on_accel)}")


# ------------------------------------------------ 12. overload (ISSUE 10)
def run_overload(emit, n: int | None = None, max_overhead: float = 0.03,
                 min_top_slo: float = 0.99, smoke: bool = False,
                 reps: int = 3):
    """Overload survival (ISSUE 10): prewarm + reclamation + idle floor.

    Prewarm: a 20x MMPP burst over the 3-device fleet. The reactive baseline
    eats the cold-start storm at each burst front (its warm pool matches the
    quiet-phase rate); the predictive pre-warmer must forecast the regime
    switches and spawn keep-alive containers ahead of the fronts, strictly
    cutting the cold-start count.

    Reclamation: sustained bursts saturating ONE device of the fleet (the
    burst lands on a single hot edge; on a uniformly saturated fleet a
    preempted task's re-placement just moves the pressure next door, so the
    single-device case is where reclamation has physics to exploit — the
    masked re-placement forces victims to cloud). Lower-tier work already
    placed on the hot device is preempted and demoted; the top (non-
    sheddable) tier must clear ``min_top_slo`` attainment that the
    reclamation-off serve visibly misses, with real downgrades (not sheds).

    Policies-off floor: stage-timed best-of-reps like ``run_chaos`` — a
    runtime with BOTH policies armed but never triggering (forecaster fold
    runs every chunk, pressure test runs every batch) must stay bit-
    identical per record to the plain runtime and within ``max_overhead``
    of its rate. Judged at full size; smoke relaxes the bar (shared CI
    runners throttle) but keeps parity at full strength.
    """
    from repro.core.decision import MinCostPolicy
    from repro.core.faults import SLOTier
    from repro.core.overload import PrewarmPolicy, ReclamationPolicy

    if n is None:
        n = 20_000 if common.REDUCED else 100_000
    banner(f"bench_runtime/overload — prewarm + reclamation + idle floor "
           f"({n:,} tasks)")
    twin, models = fit_app("FD", seed=0, n_inputs=120, configs=CONFIGS)

    def runtime(policy=None, fleet=FLEET_SPEEDS, **knobs):
        pred = build_fleet_predictor(models, dict(fleet), configs=CONFIGS)
        eng = DecisionEngine(predictor=pred, policy=policy or MinLatencyPolicy(
            c_max=C_MAX, alpha=ALPHA))
        backend = TwinBackend(twin, seed=11, edge_names=tuple(fleet),
                              edge_speed=dict(fleet))
        return PlacementRuntime(eng, backend, **knobs)

    # ---- prewarm: 20x bursts, reactive vs predictive over the full fleet
    n_pw = 5_000
    burst = BurstyWorkload(rate_per_s=2.0, size_sampler=twin.sample_input,
                           burst_multiplier=20.0, mean_quiet_s=20.0,
                           mean_burst_s=5.0, seed=3).generate(n_pw)
    reactive = runtime().serve(burst)
    rt_pw = runtime(prewarm=PrewarmPolicy(count=4))
    t0 = time.perf_counter()
    warmed = rt_pw.serve(burst)
    pw_s = time.perf_counter() - t0
    cold_re = int(reactive.records.actual_cold.sum())
    cold_pw = int(warmed.records.actual_cold.sum())
    print(f"prewarm           reactive {cold_re:>4d} cold starts  "
          f"predictive {cold_pw:>4d}  "
          f"({rt_pw.overload.forecaster.n_triggers} bursts forecast, "
          f"{len(rt_pw.overload.prewarm_log)} containers spawned)")
    assert rt_pw.overload.forecaster.n_triggers > 0, \
        "the burst forecaster never fired on a 20x MMPP workload"
    assert cold_pw < cold_re, \
        f"predictive prewarm ({cold_pw} cold starts) must beat the " \
        f"reactive baseline ({cold_re})"
    emit(f"runtime/overload_prewarm[{n_pw}]", pw_s / n_pw * 1e6,
         f"n={n_pw};cold_reactive={cold_re};cold_prewarm={cold_pw};"
         f"triggers={rt_pw.overload.forecaster.n_triggers}")

    # ---- reclamation: bursts saturating one hot device, tiered 10/45/45
    n_rc, chunk, top_slo_ms = 4_000, 64, 180_000.0
    hot = {"edge0": 1.0}
    tasks = BurstyWorkload(rate_per_s=0.05, size_sampler=twin.sample_input,
                           burst_multiplier=5.0, mean_quiet_s=150.0,
                           mean_burst_s=30.0, seed=3).generate(n_rc)
    for i, t in enumerate(tasks):
        t.tier = 0 if i % 10 == 0 else (1 if i % 2 else 2)
    recl = ReclamationPolicy(
        tiers=(SLOTier(top_slo_ms, sheddable=False),
               SLOTier(3_000.0), SLOTier(2_500.0)),
        shares=(8.0, 1.0, 1.0), headroom=0.1)
    # deadline 1e9 keeps placement all-edge: the policy itself must not
    # relieve the device, only reclamation may
    off = runtime(MinCostPolicy(deadline_ms=1e9), hot).serve_stream(
        tasks, chunk_size=chunk)
    rt_rc = runtime(MinCostPolicy(deadline_ms=1e9), hot, reclamation=recl)
    t0 = time.perf_counter()
    on = rt_rc.serve_stream(tasks, chunk_size=chunk)
    rc_s = time.perf_counter() - t0
    slo_off = off.slo_attainment(top_slo_ms, tier=0)
    slo_on = on.slo_attainment(top_slo_ms, tier=0)
    moved = sum(1 for e in rt_rc.overload.reclaim_log if e[6])
    print(f"reclamation       top-tier SLO {slo_off:6.2%} -> {slo_on:6.2%}  "
          f"({len(rt_rc.overload.reclaim_log)} preempted, {moved} moved to "
          f"cloud, {on.n_downgraded} demoted, shed {on.n_shed})")
    assert slo_on >= min_top_slo, \
        f"top-tier SLO {slo_on:.2%} under reclamation below the " \
        f"{min_top_slo:.0%} floor"
    assert slo_on > slo_off, \
        "reclamation must visibly improve top-tier attainment"
    assert on.n_downgraded > 0 and moved > 0, \
        "reclamation must demote real (moved) lower-tier work, not shed it"
    emit(f"runtime/overload_reclaim[{n_rc}]", rc_s / n_rc * 1e6,
         f"n={n_rc};slo_off={slo_off:.4f};slo_on={slo_on:.4f};"
         f"preempted={len(rt_rc.overload.reclaim_log)};"
         f"downgraded={on.n_downgraded}")

    # ---- policies-off floor: both policies armed but idle. Stage-timed
    # best-of-reps (see run_chaos: whole-serve timing would measure
    # placement-stage noise, not the armed hooks this gates). The stages
    # mirror serve(batched=True) exactly, hooks included.
    idle_pw = PrewarmPolicy(min_gaps=10**9)           # fold runs, no trigger
    idle_rc = ReclamationPolicy(tiers=(SLOTier(1e15, sheddable=False),
                                       SLOTier(1e12)), shares=(1.0, 1.0))
    tasks = _bursty(twin, n, rate_per_s=3.0, seed=3)
    for t in tasks:
        t.tier = 0 if t.idx % 4 else 1
    _warm_model_caches(models, tasks)
    stage_s = {"plain": [float("inf")] * 2, "armed": [float("inf")] * 2}
    recs = {}
    for _ in range(reps):
        for tag, rt in (("plain", runtime()),
                        ("armed", runtime(prewarm=idle_pw,
                                          reclamation=idle_rc))):
            t0 = time.perf_counter()
            rt._pre_place(tasks)
            rt._snapshot_horizons()
            d = rt.engine.place_many(tasks, edge_queues=rt.edge_queues)
            stage_s[tag][0] = min(stage_s[tag][0], time.perf_counter() - t0)
            t0 = time.perf_counter()
            r = rt._execute_decisions(tasks, d)
            rt._post_execute(r)
            recs[tag] = r
            stage_s[tag][1] = min(stage_s[tag][1], time.perf_counter() - t0)
    identical = all(
        np.array_equal(getattr(recs["plain"], c), getattr(recs["armed"], c))
        for c in ("actual_latency_ms", "actual_cost", "completion_ms",
                  "target_codes", "downgraded"))
    plain_s, armed_s = (sum(stage_s[t]) for t in ("plain", "armed"))
    overhead = armed_s / max(plain_s, 1e-12) - 1.0
    print(f"policies-off      plain {n / plain_s:>10,.0f} t/s  "
          f"armed-idle {n / armed_s:>10,.0f} t/s  overhead {overhead:+6.1%}  "
          f"identical={identical}")
    assert identical, "armed-but-idle policies diverged from the plain serve"
    assert overhead <= max_overhead, \
        f"policies-off overhead {overhead:+.1%} above the " \
        f"{max_overhead:.0%} floor"
    emit(f"runtime/overload_off[{n}]", armed_s / n * 1e6,
         f"n={n};overhead={overhead:+.3f}")


# ------------------------------------------------------------------- driver
def run(emit, n: int | None = None):
    run_decision(emit, n=n)
    run_serve(emit, n=n)
    run_twin_exec(emit)
    run_fleet(emit)
    run_live_async(emit)
    if not common.REDUCED and n is None:
        run_million(emit)
        run_streaming(emit)
        run_sharded(emit)
        run_trace_planner(emit)
        run_jax_core(emit)
        run_residency(emit)
        run_chaos(emit)
        run_overload(emit)


def run_smoke(emit):
    """Seconds-long fleet perf smoke for CI: small sizes, relaxed bars
    (shared CI runners throttle unpredictably; the 10x/5x acceptance bars are
    judged at full size on the saturated case). The mixed cases only have to
    not be slowdowns — their value in CI is the bit-parity check. The live
    async-overlap floor is likewise relaxed to 1.3x in smoke (the ≥2x
    acceptance bar assumes ≥2 unthrottled cores and the full task count)."""
    run_decision(emit, n=8_000, min_speedup=4.0, mixed_min_speedup=1.0)
    run_serve(emit, n=8_000, min_speedup=3.0)
    run_twin_exec(emit, n=20_000, min_speedup=3.0, mixed_min_speedup=1.0)
    run_fleet(emit, n=1_200)
    run_live_async(emit, n=60, min_speedup=1.3)
    # streaming-scale smoke: small n, tracemalloc ceiling, relaxed rate floor
    # (shared CI runners throttle; the 10M scenario + >=1x floor run full)
    run_streaming(emit, n=200_000, n_oneshot=200_000, chunk=32_768,
                  min_rel_rate=0.7, smoke=True)
    # sharded smoke: tiny shards are overhead-dominated even on a 4-core
    # runner, so the floor is sanity-only — the cross-mode per-record parity
    # checks inside run_sharded are the smoke's real gate (the 2x acceptance
    # floor is judged at full size on >=4 unthrottled cores)
    run_sharded(emit, n_per_app=60_000, chunk=16_384, min_speedup=0.5)
    # trace replay + planner smoke: same 8-candidate search on a 50k-task
    # trace; only the replay-rate floor is relaxed (throttled runners), the
    # parity and cheapest-meets-SLO assertions hold at full strength
    run_trace_planner(emit, n=50_000, chunk=16_384, max_rel=1.4, smoke=True)
    # jax-core smoke: small-N bit-parity (interpret) + decision-equality
    # (compiled) + the no-retrace compile-cache gate; the >=2x speedup floor
    # is judged at full size on an accelerator only
    run_jax_core(emit, n=3_000, chunk=1_024, smoke=True)
    # residency smoke: the sync-count + decision-parity gates (resident vs
    # per-chunk, plus the 1-sync-per-fallback-exit budget) hold at full
    # strength; only the resident-vs-per-chunk rate floor is deferred to
    # full size on an accelerator
    run_residency(emit, n=3_000, chunk=1_024, smoke=True)
    # chaos smoke: the empty-FaultSpec bit-parity gate holds at full
    # strength; only the 3% overhead bar is relaxed (throttled runners —
    # the floor is judged at full size), plus the 1-of-3-devices-down
    # degradation scenario with its top-tier SLO assertion
    run_chaos(emit, n=8_000, max_overhead=0.25, smoke=True)
    # overload smoke: the prewarm cold-start cut, the reclamation SLO gate,
    # and the armed-idle bit-parity all hold at full strength (their
    # scenarios are fixed-size); only the 3% policies-off overhead bar is
    # relaxed (throttled runners — the floor is judged at full size)
    run_overload(emit, n=8_000, max_overhead=0.25, smoke=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=None)
    args = p.parse_args()
    from repro import compile_cache

    compile_cache.configure()
    from benchmarks.common import CsvSink

    sink = CsvSink()
    run(sink, n=args.n)
    print(sink.dump())


if __name__ == "__main__":
    main()
