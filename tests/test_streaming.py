"""Streaming sharded serve (ISSUE 5): chunked pipeline + multi-app shards.

Covers:
- ``serve_stream`` ≡ one-shot ``serve(batched=True)`` PER RECORD for chunk
  sizes from 1 upward, including boundaries landing inside speculate-and-
  repair segments (small ``COLUMNAR_CHUNK`` + bursty edge/cloud oscillation);
- ``TaskChunk`` columnar workloads: lazy views, slicing, bit-identical
  ``chunks()`` streams for Poisson (block sampler) and Bursty (scalar walk);
- constant-memory mode (``keep_tasks=False``): metrics backed by the arena's
  arrival/index columns, synthesized placeholder task views;
- hedged policies stream through the per-task fallback path, bit-identical;
- out-of-arrival-order streams fall back to the walk exactly like one-shot;
- ``RecordArena``: geometric growth, in-place merge, cross-table code remap
  (hedge ``-1`` passthrough), equivalence with ``RecordBatch.from_records``;
- the ``(id(model), comp_feature)`` GBRT step-table cache: shared across
  chunks and Predictors, invalidated by swapping in a fresh model object;
- always-warm targets never carry a cold component stack in
  ``predict_batch``;
- ``ShardedRuntime``: thread/process/sequential modes produce bit-identical
  per-shard results; factory validation for process mode.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import repro.core.decision as decision_mod
import repro.core.predictor as predictor_mod
from repro.core.decision import (
    DecisionEngine,
    HedgedPolicy,
    MinCostPolicy,
    MinLatencyPolicy,
)
from repro.core.fit import build_fleet_predictor, build_predictor, fit_app
from repro.core.multiapp import AppShard, ShardedRuntime, serve_sharded
from repro.core.records import RecordArena, RecordBatch, TaskRecord
from repro.core.runtime import PlacementRuntime, TwinBackend
from repro.core.workload import BurstyWorkload, TaskChunk, TaskInput, task_arrays

CONFIGS = (1280, 1536, 1792)
FLEET = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}
NAMES = tuple(FLEET)

RECORD_COLS = ("predicted_latency_ms", "predicted_cost", "actual_latency_ms",
               "actual_cost", "allowed_cost", "completion_ms", "queue_wait_ms",
               "exec_ms", "hedge_exec_ms", "predicted_cold", "actual_cold",
               "feasible", "hedged")


@pytest.fixture(scope="module")
def ir_setup():
    return fit_app("IR", seed=0, n_inputs=120, configs=CONFIGS)


@pytest.fixture(scope="module")
def stt_setup():
    return fit_app("STT", seed=0, n_inputs=120, configs=CONFIGS)


def _runtime(twin, models, c_max=6e-6, alpha=0.05, policy=None, seed=11):
    pred = build_fleet_predictor(models, dict(FLEET), configs=CONFIGS)
    eng = DecisionEngine(
        predictor=pred,
        policy=policy if policy is not None
        else MinLatencyPolicy(c_max=c_max, alpha=alpha))
    backend = TwinBackend(twin, seed=seed, edge_names=NAMES, edge_speed=FLEET)
    return PlacementRuntime(eng, backend)


def _bursty(twin, n, seed=31):
    return BurstyWorkload(rate_per_s=4.0, size_sampler=twin.sample_input,
                          burst_multiplier=8.0, mean_quiet_s=10.0,
                          mean_burst_s=6.0, seed=seed).generate(n)


def assert_records_equal(a: RecordBatch, b: RecordBatch):
    assert len(a) == len(b)
    assert list(a.targets) == list(b.targets)
    for col in RECORD_COLS:
        assert np.array_equal(getattr(a, col), getattr(b, col)), col
    assert np.array_equal(a.arrival_ms, b.arrival_ms)


# -------------------------------------------------- serve_stream bit-parity
def test_serve_stream_equals_one_shot_across_chunk_sizes(ir_setup, monkeypatch):
    """The headline guarantee: chunking changes where passes pause, never
    what they compute — per-record equality for every chunk size, with
    boundaries forced inside repair segments (small speculation windows,
    bursty edge/cloud oscillation → repairs on the one-shot side too)."""
    monkeypatch.setattr(decision_mod, "COLUMNAR_CHUNK", 64)
    twin, models = ir_setup
    tasks = _bursty(twin, 1200)
    ref = _runtime(twin, models).serve(tasks, batched=True)
    for chunk_size in (1, 7, 53, 256, 1200, 5000):
        rt = _runtime(twin, models)
        res = rt.serve_stream(tasks, chunk_size=chunk_size)
        assert_records_equal(res.records, ref.records)
        assert rt.stream_stats["n"] == 1200
    # and the repair machinery was actually exercised somewhere in the stream
    rt = _runtime(twin, models)
    rt.serve_stream(tasks, chunk_size=53)
    assert rt.stream_stats["repairs"] + rt.stream_stats["walked"] > 0


def test_serve_stream_task_chunk_and_chunk_iterator(ir_setup):
    twin, models = ir_setup
    tasks = _bursty(twin, 400, seed=9)
    ref = _runtime(twin, models).serve(tasks, batched=True)

    res = _runtime(twin, models).serve_stream(
        TaskChunk.from_tasks(tasks), chunk_size=97)
    assert_records_equal(res.records, ref.records)

    # a generator of ready TaskChunks (the constant-memory spelling)
    def chunk_gen():
        tc = TaskChunk.from_tasks(tasks)
        for lo in range(0, len(tc), 119):
            yield tc[lo:lo + 119]

    res2 = _runtime(twin, models).serve_stream(chunk_gen())
    assert_records_equal(res2.records, ref.records)

    # an iterator of plain TaskInputs is buffered into chunk_size lists
    res3 = _runtime(twin, models).serve_stream(iter(tasks), chunk_size=61)
    assert_records_equal(res3.records, ref.records)


def test_serve_stream_keep_tasks_false_constant_memory_result(ir_setup):
    twin, models = ir_setup
    tasks = _bursty(twin, 300, seed=12)
    ref = _runtime(twin, models).serve(tasks, batched=True)
    res = _runtime(twin, models).serve_stream(
        TaskChunk.from_tasks(tasks), chunk_size=64, keep_tasks=False)
    assert len(res.records.tasks) == 0
    assert np.array_equal(res.records.arrival_ms,
                          np.array([t.arrival_ms for t in tasks]))
    assert res.records.task_idx is not None
    assert res.records.task_idx.tolist() == [t.idx for t in tasks]
    # metrics all work without task objects
    assert res.avg_actual_latency_ms == ref.avg_actual_latency_ms
    assert res.total_actual_cost == ref.total_actual_cost
    assert res.makespan_ms == ref.makespan_ms
    assert {d: s.n_tasks for d, s in res.device_summaries().items()} == \
        {d: s.n_tasks for d, s in ref.device_summaries().items()}
    # per-record views synthesize placeholder tasks
    rec = res.records[5]
    assert rec.task.meta == {"streamed": True}
    assert rec.task.idx == 5
    assert rec.task.arrival_ms == tasks[5].arrival_ms
    assert np.isnan(rec.task.size)


def test_serve_stream_hedged_policy_fallback_path(ir_setup):
    """Hedged (non-columnar) policies stream through the per-task walk +
    hedge-plan execution — still bit-identical to one-shot, still chunked."""
    twin, models = ir_setup
    tasks = twin.workload(200, seed=5)

    def run(stream):
        policy = HedgedPolicy(MinLatencyPolicy(c_max=8e-5, alpha=0.0),
                              hedge_threshold_ms=1500.0)
        rt = _runtime(twin, models, policy=policy, seed=17)
        if stream:
            return rt.serve_stream(tasks, chunk_size=37)
        return rt.serve(tasks, batched=True)

    a, b = run(True), run(False)
    assert int(np.count_nonzero(a.records.hedged)) > 0
    assert_records_equal(a.records, b.records)
    hc_a = [r.hedge_target for r in a.records]
    hc_b = [r.hedge_target for r in b.records]
    assert hc_a == hc_b


def test_serve_stream_unsorted_stream_falls_back_to_walk(ir_setup):
    """A chunk arriving before the stream's high-water mark flips the whole
    remaining stream to the per-task walk — matching what one-shot
    ``serve(batched=True)`` does when it sees the full unsorted list."""
    twin, models = ir_setup
    tasks = twin.workload(120, seed=6)
    for i, t in enumerate(tasks):
        if i % 7 == 3:
            t.arrival_ms += 5e5  # future spikes: later chunks start "early"
    ref = _runtime(twin, models, c_max=8e-5, alpha=0.02).serve(
        tasks, batched=True)
    rt = _runtime(twin, models, c_max=8e-5, alpha=0.02)
    res = rt.serve_stream(tasks, chunk_size=16)
    assert_records_equal(res.records, ref.records)
    assert rt.stream_stats["walked"] > 0


def test_serve_stream_chunk_size_validation_and_empty(ir_setup):
    twin, models = ir_setup
    rt = _runtime(twin, models)
    with pytest.raises(ValueError, match="chunk_size"):
        rt.serve_stream([], chunk_size=0)
    res = rt.serve_stream([], chunk_size=8)
    assert res.n == 0


# ------------------------------------------------------ columnar workloads
def test_poisson_chunks_bit_identical_to_generate(stt_setup):
    twin, _ = stt_setup
    wl = twin.poisson(seed=5)
    tasks = wl.generate(700)
    chunks = list(wl.chunks(700, chunk_size=64))
    assert all(isinstance(c, TaskChunk) for c in chunks)
    idx = np.concatenate([c.idx for c in chunks])
    arr = np.concatenate([c.arrival_ms for c in chunks])
    size = np.concatenate([c.size for c in chunks])
    nbytes = np.concatenate([c.bytes for c in chunks])
    assert idx.tolist() == [t.idx for t in tasks]
    assert arr.tolist() == [t.arrival_ms for t in tasks]
    assert size.tolist() == [t.size for t in tasks]
    assert nbytes.tolist() == [t.bytes for t in tasks]


def test_bursty_chunks_bit_identical_to_generate(ir_setup):
    twin, _ = ir_setup
    wl = BurstyWorkload(rate_per_s=4.0, size_sampler=twin.sample_input, seed=3)
    tasks = wl.generate(500)
    chunks = list(wl.chunks(500, chunk_size=77))
    arr = np.concatenate([c.arrival_ms for c in chunks])
    size = np.concatenate([c.size for c in chunks])
    assert arr.tolist() == [t.arrival_ms for t in tasks]
    assert size.tolist() == [t.size for t in tasks]
    # the list form still carries the burst flag
    assert {t.meta["burst"] for t in tasks} == {False, True}


def test_sample_input_batch_matches_scalar_loop(ir_setup, stt_setup):
    for twin in (ir_setup[0], stt_setup[0]):
        r1 = np.random.default_rng(4)
        r2 = np.random.default_rng(4)
        got_s, got_b = twin.sample_input_batch(r1, 50)
        exp = [twin.sample_input(r2) for _ in range(50)]
        assert got_s.tolist() == [s for s, _ in exp]
        assert got_b.tolist() == [b for _, b in exp]


def test_task_chunk_views_and_task_arrays(ir_setup):
    twin, _ = ir_setup
    tasks = twin.workload(20, seed=2)
    tc = TaskChunk.from_tasks(tasks)
    assert len(tc) == 20 and bool(tc)
    assert tc[3].arrival_ms == tasks[3].arrival_ms
    assert [t.idx for t in tc[5:9]] == [5, 6, 7, 8]
    idx, arr, size, nbytes = task_arrays(tc)
    assert arr is tc.arrival_ms  # no copy on the columnar path
    idx2, arr2, size2, nbytes2 = task_arrays(tasks)
    assert arr2.tolist() == arr.tolist()
    assert size2.tolist() == size.tolist()


# ------------------------------------------------------------- RecordArena
def _mk_record(i, target="a", hedge=None):
    return TaskRecord(
        task=TaskInput(idx=i, arrival_ms=float(i), size=1.0, bytes=1.0),
        target=target, predicted_latency_ms=i * 1.5, predicted_cost=i * 0.1,
        actual_latency_ms=i * 2.0, actual_cost=i * 0.2,
        predicted_cold=bool(i % 2), actual_cold=bool(i % 3 == 0),
        allowed_cost=float(i), feasible=bool(i % 4), completion_ms=i * 3.0,
        hedged=hedge is not None, queue_wait_ms=0.5 * i, exec_ms=0.25 * i,
        hedge_target=hedge, hedge_exec_ms=1.0 if hedge else 0.0)


def test_arena_growth_and_equivalence_with_from_records():
    records = [_mk_record(i, target=("a", "b", "c")[i % 3],
                          hedge=("b" if i % 5 == 0 else None))
               for i in range(3000)]
    ref = RecordBatch.from_records(records)
    arena = RecordArena(keep_tasks=True, capacity=4)
    # many small appends with shifting per-chunk target tables → growth +
    # remap both exercised
    for lo in range(0, 3000, 17):
        arena.append(records[lo:lo + 17])
    assert len(arena) == 3000
    got = arena.finish()
    assert len(got) == 3000
    for col in RECORD_COLS:
        assert np.array_equal(getattr(got, col), getattr(ref, col)), col
    assert list(got.targets) == list(ref.targets)
    # hedge codes survive the remap, -1 passthrough included
    assert [got.target_names[c] if c >= 0 else None
            for c in got.hedge_codes.tolist()] == \
        [r.hedge_target for r in records]
    assert got.tasks[5] is records[5].task
    # dtypes preserved
    assert got.predicted_cold.dtype == np.bool_
    assert got.target_codes.dtype == np.int64


def test_arena_merges_disjoint_target_tables():
    a = RecordBatch.from_records([_mk_record(0, "x"), _mk_record(1, "y")])
    b = RecordBatch.from_records([_mk_record(2, "z"), _mk_record(3, "x")])
    arena = RecordArena()
    arena.append(a)
    arena.append(b)
    got = arena.finish()
    assert list(got.targets) == ["x", "y", "z", "x"]
    assert got.target_names == ("x", "y", "z")


def test_arena_empty_and_doubling():
    arena = RecordArena()
    assert len(arena.finish()) == 0
    arena.append([])
    assert arena.n == 0
    arena.append([_mk_record(i) for i in range(3)])
    cap0 = arena._cap
    arena.append([_mk_record(i) for i in range(cap0)])
    assert arena._cap >= cap0 * 2  # geometric doubling, not +chunk
    assert arena.nbytes > 0
    got = arena.finish()
    assert len(got) == 3 + cap0
    # rows already appended are never rewritten: the finished view (rows AND
    # its snapshot of the target table) is immune to later appends
    arena.append([_mk_record(99, "zz")])
    assert len(got) == 3 + cap0
    assert "zz" not in got.target_names
    assert "zz" in arena.finish().target_names


def test_arena_keep_tasks_false_columns():
    arena = RecordArena(keep_tasks=False)
    arena.append([_mk_record(i) for i in range(5)])
    got = arena.finish()
    assert got.tasks == []
    assert got.arrivals.tolist() == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert got.task_idx.tolist() == [0, 1, 2, 3, 4]
    assert got[2].task.meta == {"streamed": True}


# ---------------------------------------- GBRT step-table cache (satellite)
def test_const1_cache_shared_across_chunks_and_predictors(stt_setup, monkeypatch):
    """The step table is derived once per (model identity, comp_feature) —
    chunked serving at ANY chunk size, and fresh Predictor objects over the
    same fitted models, reuse it instead of re-deriving per call."""
    twin, models = stt_setup
    model = models.comp_cloud
    calls = {"n": 0}
    orig = type(model).const1_table

    def counting(self, c):
        calls["n"] += 1
        return orig(self, c)

    monkeypatch.setattr(type(model), "const1_table", counting)
    predictor_mod._CONST1_TABLES.clear()
    model.__dict__.pop("_const1_tables", None)

    pred = build_predictor(models, configs=CONFIGS)
    tasks = twin.workload(40, seed=1)
    for lo in range(0, 40, 4):  # 10 small chunks, incl. sub-64-row ones
        pred.predict_batch(tasks[lo:lo + 4])
    assert calls["n"] == len(CONFIGS)  # one derivation per memory config
    # a different Predictor over the SAME model objects also hits the cache
    build_predictor(models, configs=CONFIGS).predict_batch(tasks)
    assert calls["n"] == len(CONFIGS)


def test_const1_cache_invalidated_by_model_swap(stt_setup):
    """Online-refit contract: swapping in a fresh model object must never
    serve the old model's table (identity-keyed with a weakref guard)."""
    import dataclasses

    twin, models = stt_setup
    predictor_mod._CONST1_TABLES.clear()
    x = np.linspace(1e4, 4e5, 200)
    old = models.comp_cloud
    got_old = predictor_mod.gbrt_predict_const(old, x, float(CONFIGS[0]))
    assert np.array_equal(got_old,
                          old.predict(np.stack([x, np.full(200, float(CONFIGS[0]))], 1)))
    # a refit swaps in a FRESH object whose trees differ
    fresh = dataclasses.replace(old, leaves=old.leaves * 2.0)
    fresh.__dict__.pop("_const1_tables", None)
    got_fresh = predictor_mod.gbrt_predict_const(fresh, x, float(CONFIGS[0]))
    assert not np.array_equal(got_fresh, got_old)
    assert np.array_equal(
        got_fresh,
        fresh.predict(np.stack([x, np.full(200, float(CONFIGS[0]))], 1)))


def test_gbrt_predict_const_bit_identical_to_stacked(stt_setup):
    twin, models = stt_setup
    x = np.linspace(1e4, 4e5, 500)
    for c in CONFIGS:
        feats = np.stack([x, np.full(500, float(c))], axis=1)
        assert np.array_equal(
            predictor_mod.gbrt_predict_const(models.comp_cloud, x, float(c)),
            models.comp_cloud.predict(feats))


# ----------------------------------- always-warm cold-skip (satellite)
def test_predict_batch_drops_cold_stack_for_always_warm_targets(ir_setup):
    """A custom always-warm target that naively hands back ``cold = warm``
    must not have the duplicate stack carried (or its latency re-summed)."""
    from repro.core.predictor import Predictor

    class NaiveEdge:
        name = "naive"
        is_edge = True

        def predict_components_batch(self, sizes, nbytes, quantile=None):
            warm = {"comp": np.asarray(sizes, float) * 2.0,
                    "store": np.full(sizes.shape[0], 3.0)}
            return warm, dict(warm)  # the wasteful cold = warm copy

        def predict_components(self, task, cold=False, quantile=None):
            return {"comp": task.size * 2.0, "store": 3.0}

        def cost(self, comp_ms):
            return 0.0

        def occupancy_ms(self, components):
            return components["comp"]

    twin, models = ir_setup
    base = build_predictor(models, configs=CONFIGS)
    pred = Predictor(cloud_targets=base.cloud_targets, edge_target=NaiveEdge())
    batch = pred.predict_batch(twin.workload(10, seed=3))
    tb = batch.edges["naive"]
    assert tb.cold is None and tb.cold_latency is None
    # and the per-task view never reports a cold edge
    view = pred.predict_at(batch, 0, 0.0)
    assert view["naive"].cold is False


# ------------------------------------------------------- sharded serving
def _shard_runtime(app, setups, c_max=0.0):
    twin, models = setups[app]
    pred = build_fleet_predictor(models, dict(FLEET), configs=CONFIGS)
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=c_max, alpha=0.0))
    backend = TwinBackend(twin, seed=7, edge_names=NAMES, edge_speed=FLEET)
    return PlacementRuntime(eng, backend)


def _shard_workload(app, setups, n):
    return setups[app][0].poisson(seed=3).chunks(n, chunk_size=256)


@pytest.fixture(scope="module")
def app_setups(ir_setup, stt_setup):
    return {"IR": ir_setup, "STT": stt_setup}


def _make_shards(setups, n=600):
    return [AppShard(name=app,
                     runtime=functools.partial(_shard_runtime, app, setups),
                     workload=functools.partial(_shard_workload, app, setups, n),
                     chunk_size=256)
            for app in setups]


def test_sharded_thread_equals_sequential_per_record(app_setups):
    shards = _make_shards(app_setups)
    seq = ShardedRuntime(shards).serve(parallel=False)
    thr = serve_sharded(shards)  # thread mode default
    assert seq.mode == "sequential" and thr.mode == "thread"
    assert set(seq.results) == set(thr.results) == set(app_setups)
    for app in app_setups:
        assert_records_equal(thr.results[app].records, seq.results[app].records)
    assert thr.n == seq.n == 600 * len(app_setups)
    table = thr.table()
    for app in app_setups:
        assert app in table
    assert "TOTAL" in table


def test_sharded_process_mode_equals_sequential(app_setups):
    shards = _make_shards(app_setups, n=200)
    seq = ShardedRuntime(shards).serve(parallel=False)
    proc = ShardedRuntime(shards).serve(parallel=True, use_processes=True)
    assert proc.mode == "process"
    for app in app_setups:
        assert_records_equal(proc.results[app].records,
                             seq.results[app].records)


def _jax_shard_runtime(app, setups):
    rt = _shard_runtime(app, setups)
    rt.engine.array_backend = "jax"
    return rt


def test_sharded_process_children_never_touch_jax(app_setups, monkeypatch):
    """Spawned children are numpy-only by construction: with JAX unable to
    initialize any backend in them, process mode still serves, per record
    identical to the sequential run — and a shard asking for the device
    core is refused instead of reaching for a chip the parent may hold."""
    shards = _make_shards(app_setups, n=64)
    seq = ShardedRuntime(shards).serve(parallel=False)
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")  # children only
    proc = ShardedRuntime(shards).serve(parallel=True, use_processes=True)
    for app in app_setups:
        assert_records_equal(proc.results[app].records,
                             seq.results[app].records)
    shard = AppShard(name="IR",
                     runtime=functools.partial(_jax_shard_runtime, "IR",
                                               app_setups),
                     workload=functools.partial(_shard_workload, "IR",
                                                app_setups, 16))
    with pytest.raises(ValueError, match="numpy"):
        ShardedRuntime([shard]).serve(parallel=True, use_processes=True)


def test_sharded_process_mode_requires_factories(app_setups):
    rt = _shard_runtime("IR", app_setups)
    shard = AppShard(name="IR", runtime=rt, workload=[])
    with pytest.raises(ValueError, match="factories"):
        ShardedRuntime([shard]).serve(parallel=True, use_processes=True)


def test_sharded_validation(app_setups):
    shards = _make_shards(app_setups, n=10)
    with pytest.raises(ValueError, match="duplicate"):
        ShardedRuntime(shards + [shards[0]])
    with pytest.raises(ValueError, match="at least one"):
        ShardedRuntime([])

    bad = AppShard(name="bad", runtime=lambda: 42, workload=[])
    with pytest.raises(TypeError, match="PlacementRuntime"):
        bad.resolve_runtime()


def test_sharded_stream_stats_and_walls(app_setups):
    shards = _make_shards(app_setups, n=300)
    res = ShardedRuntime(shards).serve(parallel=False)
    for app in app_setups:
        assert res.stream_stats[app]["n"] == 300
        assert res.wall_s[app] > 0.0
    assert res.elapsed_s >= max(res.wall_s.values()) * 0.99
