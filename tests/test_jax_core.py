"""Device-resident placement core (ISSUE 7): jax serve ≡ numpy serve.

Covers:
- the parity contract: ``serve_stream(array_backend="jax_interpret")`` is
  BIT-IDENTICAL per record to the numpy oracle — every float column, every
  target — across MinCost/MinLatency × 1-/3-device fleets × chunk sizes
  {1, 53, 4096}, with decision-chunk boundaries forced inside repair
  segments (small ``COLUMNAR_CHUNK``, bursty edge/cloud oscillation);
- compiled mode (``array_backend="jax"``): decision-identical targets and
  float columns within tolerance (XLA contracts mul+add chains into FMAs,
  so compiled floats may differ in the last ulp);
- load balancers (RoundRobin/Random) consume their nomination state exactly
  once per chunk — parity holds and the balancer cursor matches numpy's;
- fallbacks: hedged policies, out-of-arrival-order streams and
  ``record_decisions`` take the numpy path with identical results
  (``engine.jax_stats`` stays unset);
- ``array_backend`` validation on both ``DecisionEngine`` and
  ``serve_stream``, and ``serve_stream`` restoring the engine's backend;
- the per-engine core cache (``core_for``) and the jit compile caches: a
  second same-shape chunk must NOT retrace (``compile_stats`` stable);
- ``GBRT.predict_jax`` operand hosting: cached per model identity,
  invalidated by swapping in a fresh model;
- a hypothesis property (skipped when hypothesis is missing): random
  Poisson-ish streams keep interpret parity record-for-record.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("jax")

import repro.core.decision as decision_mod
from repro.core import gbrt as gbrt_mod
from repro.core import jax_core
from repro.core.decision import (
    DecisionEngine,
    HedgedPolicy,
    MinCostPolicy,
    MinLatencyPolicy,
    RandomBalancer,
    RoundRobinBalancer,
)
from repro.core.fit import build_fleet_predictor, fit_app
from repro.core.gbrt import GBRT, GBRTConfig
from repro.core.records import RecordBatch
from repro.core.runtime import PlacementRuntime, TwinBackend
from repro.core.workload import BurstyWorkload, TaskInput

CONFIGS = (1280, 1536, 1792)
FLEET3 = {"edge0": 1.0, "edge1": 1.0, "edge2": 0.6}
FLEET1 = {"edge0": 1.0}

RECORD_COLS = ("predicted_latency_ms", "predicted_cost", "actual_latency_ms",
               "actual_cost", "allowed_cost", "completion_ms", "queue_wait_ms",
               "exec_ms", "hedge_exec_ms", "predicted_cold", "actual_cold",
               "feasible", "hedged")

FLOAT_COLS = ("predicted_latency_ms", "predicted_cost", "actual_latency_ms",
              "actual_cost", "allowed_cost", "completion_ms", "queue_wait_ms",
              "exec_ms")


@pytest.fixture(scope="module")
def ir_setup():
    return fit_app("IR", seed=0, n_inputs=120, configs=CONFIGS)


def _runtime(twin, models, fleet=FLEET3, policy=None, balancer=None, seed=11):
    pred = build_fleet_predictor(models, dict(fleet), configs=CONFIGS)
    eng = DecisionEngine(
        predictor=pred,
        policy=policy if policy is not None
        else MinLatencyPolicy(c_max=6e-6, alpha=0.05),
        balancer=balancer)
    backend = TwinBackend(twin, seed=seed, edge_names=tuple(fleet),
                          edge_speed=fleet)
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


def _policies():
    return [("min_latency", lambda: MinLatencyPolicy(c_max=6e-6, alpha=0.05)),
            ("min_cost", lambda: MinCostPolicy(deadline_ms=250.0))]


# ------------------------------------------------- interpret-mode bit parity
@pytest.mark.parametrize("policy_name,policy_fn", _policies())
@pytest.mark.parametrize("fleet", [FLEET1, FLEET3],
                         ids=["1dev", "3dev"])
@pytest.mark.parametrize("chunk_size,n", [(1, 60), (53, 300), (4096, 300)],
                         ids=["chunk1", "chunk53", "chunk4096"])
def test_interpret_bit_parity(ir_setup, monkeypatch, policy_name, policy_fn,
                              fleet, chunk_size, n):
    """The headline guarantee: the device core replays the EXACT sequential
    semantics — per-record float equality against the numpy oracle, with the
    oracle's own speculation windows forced small so repairs happen."""
    monkeypatch.setattr(decision_mod, "COLUMNAR_CHUNK", 64)
    twin, models = ir_setup
    tasks = _bursty(twin, n)
    ref = _runtime(twin, models, fleet, policy_fn()).serve_stream(
        tasks, chunk_size=chunk_size)
    rt = _runtime(twin, models, fleet, policy_fn())
    res = rt.serve_stream(tasks, chunk_size=chunk_size,
                          array_backend="jax_interpret")
    assert_records_equal(res.records, ref.records)
    stats = rt.engine.jax_stats
    assert stats is not None and stats["interpret"] and stats["n"] >= 1


@pytest.mark.parametrize("balancer_fn", [
    lambda: RoundRobinBalancer(), lambda: RandomBalancer(seed=5)],
    ids=["roundrobin", "random"])
def test_interpret_parity_with_balancers(ir_setup, balancer_fn):
    """Balancer nomination state is consumed exactly once per chunk, in
    arrival order — parity per record AND the cursor/rng advance matches."""
    twin, models = ir_setup
    tasks = _bursty(twin, 240)
    ref_rt = _runtime(twin, models, balancer=balancer_fn())
    ref = ref_rt.serve_stream(tasks, chunk_size=96)
    rt = _runtime(twin, models, balancer=balancer_fn())
    res = rt.serve_stream(tasks, chunk_size=96, array_backend="jax_interpret")
    assert_records_equal(res.records, ref.records)
    a, b = ref_rt.engine.balancer, rt.engine.balancer
    if isinstance(a, RoundRobinBalancer):
        assert a._i == b._i
    else:
        assert a.rng.integers(1 << 30) == b.rng.integers(1 << 30)


# --------------------------------------------- compiled decision equality
@pytest.mark.parametrize("policy_name,policy_fn", _policies())
def test_compiled_decision_equality(ir_setup, policy_fn, policy_name):
    """Compiled XLA fuses mul+add into FMAs, so floats may move in the last
    ulp — but every decision (target, cold, feasible) must be identical and
    every float within tolerance."""
    twin, models = ir_setup
    tasks = _bursty(twin, 400)
    ref = _runtime(twin, models, policy=policy_fn()).serve_stream(
        tasks, chunk_size=128)
    rt = _runtime(twin, models, policy=policy_fn())
    res = rt.serve_stream(tasks, chunk_size=128, array_backend="jax")
    ra, rb = ref.records, res.records
    assert list(ra.targets) == list(rb.targets)
    for col in ("predicted_cold", "actual_cold", "feasible", "hedged"):
        assert np.array_equal(getattr(ra, col), getattr(rb, col)), col
    for col in FLOAT_COLS:
        np.testing.assert_allclose(
            getattr(ra, col).astype(float), getattr(rb, col).astype(float),
            rtol=1e-9, atol=1e-12, err_msg=col)
    assert rt.engine.jax_stats is not None
    assert not rt.engine.jax_stats["interpret"]


# ------------------------------------------------- the place fixed point
@pytest.mark.parametrize("policy_name,policy_fn", _policies())
def test_place_program_replays_the_state_once_per_pass(
        ir_setup, monkeypatch, policy_name, policy_fn):
    """A traced fixed point calls the state replay twice: the first pass
    before the while loop and the pass in its body. The loop carries the
    last pass's state out to ``finalize``, so no pass runs again after it
    and a block costs exactly its ``passes`` state replays."""
    calls = {"_build_state": 0, "_build_finalize": 0}

    def counted(name):
        build = getattr(jax_core.JaxPlacementCore, name)

        def wrap(self):
            fn = build(self)

            def f(*args):
                calls[name] += 1
                return fn(*args)

            return f

        return wrap

    for name in calls:
        monkeypatch.setattr(jax_core.JaxPlacementCore, name, counted(name))
    twin, models = ir_setup
    rt = _runtime(twin, models, policy=policy_fn())
    rt.serve_stream(_bursty(twin, 100), chunk_size=64, array_backend="jax")
    assert not rt.engine.jax_stats["interpret"]
    # one finalize per traced fixed point (one per place program traced)
    assert calls["_build_finalize"] >= 1
    assert calls["_build_state"] == 2 * calls["_build_finalize"]


def _record_place(monkeypatch):
    """Per call of the compiled place program: its passes summed over the
    blocks, the rows the rank split, whether every block converged and
    whether a pool overflowed (the call is then made again); per chunk the
    core placed, ``jax_stats["passes"]``."""
    calls, passes = [], []
    orig = jax_core.JaxPlacementCore.place_chunk

    def place_chunk(self, engine, *args, **kw):
        place = self._place

        def rec(P, S):
            res = place(P, S)
            calls.append((*np.asarray(res["counts"]).tolist(),
                          bool(res["converged"]), bool(res["overflow"])))
            return res

        self._place = rec
        try:
            out = orig(self, engine, *args, **kw)
        finally:
            self._place = place
        if out is not None:
            passes.append(engine.jax_stats["passes"])
        return out

    monkeypatch.setattr(jax_core.JaxPlacementCore, "place_chunk",
                        place_chunk)
    return calls, passes


DAY_MS = 86_400_000.0
BRANCHES = ["float64", "two_float"]


def _steer(monkeypatch, branch):
    """On "two_float" the TPU branch runs on this CPU host (two-float
    arithmetic, the GBRT kernel interpreted)."""
    if branch == "two_float":
        monkeypatch.setattr(jax_core, "platform", lambda: "tpu")


# The fixed point's trajectory on fixed-seed streams, per branch: per place
# call (passes summed over its blocks, rank splits, converged, overflow), per
# chunk ``jax_stats["passes"]``. How the loop carries a pass's state must
# not move them: a change here is a change of the fixed point itself.
_IR_CALLS = [(17, 0, True, True), (17, 0, True, True), (16, 0, True, False),
             (14, 0, True, True), (14, 0, True, True), (14, 0, True, False),
             (8, 0, True, False), (2, 0, True, False)]
IR_TRAJECTORY = {"float64": (_IR_CALLS, [17, 15, 9, 3]),
                 "two_float": (_IR_CALLS, [17, 15, 9, 3])}
FD_TRAJECTORY = {
    "float64": ([(36, 0, True, True), (36, 0, True, False),
                 (35, 0, True, False), (36, 0, True, False),
                 (32, 0, True, False)], [37, 36, 37, 33]),
    "two_float": ([(36, 43, True, True), (36, 43, True, False),
                   (35, 41, True, False), (36, 39, True, False),
                   (32, 50, True, False)], [37, 36, 37, 33])}


@pytest.mark.parametrize("branch", BRANCHES)
def test_compiled_fixed_point_trajectory_on_an_ir_stream(
        ir_setup, monkeypatch, branch):
    """IR under MinLatency with a live surplus bank and the LPW edge scan,
    128-row chunks of four blocks: the compiled fixed point takes the pinned
    passes on both branches, and decides as the numpy oracle does."""
    twin, models = ir_setup
    tasks = _bursty(twin, 400)
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=128)
    _steer(monkeypatch, branch)
    calls, passes = _record_place(monkeypatch)
    rt = _runtime(twin, models)
    res = rt.serve_stream(tasks, chunk_size=128, array_backend="jax")
    assert (calls, passes) == IR_TRAJECTORY[branch]
    ra, rb = ref.records, res.records
    assert list(ra.targets) == list(rb.targets)
    for col in ("predicted_cold", "actual_cold", "feasible"):
        assert np.array_equal(getattr(ra, col), getattr(rb, col)), col
    for col in FLOAT_COLS:
        np.testing.assert_allclose(
            getattr(ra, col).astype(float), getattr(rb, col).astype(float),
            rtol=1e-9, atol=1e-12, err_msg=col)


# ------------------------------------------------- the billing order
def _fd_runtime(twin, models, configs, seed=11):
    pred = build_fleet_predictor(models, dict(FLEET3), configs=configs)
    eng = DecisionEngine(predictor=pred,
                         policy=MinCostPolicy(deadline_ms=4500.0))
    return PlacementRuntime(eng, TwinBackend(
        twin, seed=seed, edge_names=tuple(FLEET3), edge_speed=FLEET3))


@pytest.fixture(scope="module")
def fd19_setup():
    """FD on the paper's 19 Lambda configs, placed by MinCost at the Table
    III deadline, 2,048 Poisson arrivals a day into the stream."""
    from repro.core.apps import MEMORY_CONFIGS_MB
    from repro.core.workload import PoissonWorkload

    twin, models = fit_app("FD", seed=0, n_inputs=200,
                           configs=MEMORY_CONFIGS_MB)
    tasks = PoissonWorkload(rate_per_s=4.0, size_sampler=twin.sample_input,
                            seed=3).generate(2048)
    for t in tasks:
        t.arrival_ms += DAY_MS
    return twin, models, MEMORY_CONFIGS_MB, tasks


@pytest.mark.parametrize("branch", BRANCHES)
def test_compiled_mincost_decides_the_billing_order_as_float64(
        fd19_setup, monkeypatch, branch):
    """Lambda billing makes exact ties in real arithmetic that float64
    splits by one ulp; compiled float64 (reassociated) and two-float (48
    bits) each lost that order and decided hundreds of FD tasks otherwise.
    The rank table keeps the oracle's order on both branches, and the fixed
    point over its 16-block chunks takes the pinned passes."""
    twin, models, configs, tasks = fd19_setup
    ref = _fd_runtime(twin, models, configs).serve_stream(tasks,
                                                          chunk_size=512)
    _steer(monkeypatch, branch)
    calls, passes = _record_place(monkeypatch)
    rt = _fd_runtime(twin, models, configs)
    res = rt.serve_stream(tasks, chunk_size=512, array_backend="jax")
    assert (calls, passes) == FD_TRAJECTORY[branch]
    ra, rb = ref.records, res.records
    assert list(ra.targets) == list(rb.targets)
    for col in ("predicted_cold", "actual_cold", "feasible"):
        assert np.array_equal(getattr(ra, col), getattr(rb, col)), col
    for col in FLOAT_COLS:
        np.testing.assert_allclose(
            getattr(ra, col).astype(float), getattr(rb, col).astype(float),
            rtol=1e-9, atol=1e-12, err_msg=col)
    assert len(set(ra.targets)) > 3        # the order over many configs
    assert rt.stream_stats["residency"]["fallback_chunks"] == 0
    splits = rt.stream_stats["spans"]["cost_rank_splits"]
    assert (splits > 0) if branch == "two_float" else (splits == 0)


def _lambda_cost(memory_mb, quanta, rate=1.66667e-5, quantum=100.0):
    """``LambdaPricing.cost_batch``'s float64 formula."""
    return (((quanta * quantum) / 1000.0) * (memory_mb / 1024.0)) * rate


def test_cost_ranks_order_costs_exactly_as_float64():
    from repro.core.apps import MEMORY_CONFIGS_MB
    from repro.kernels import dfloat

    k = np.arange(80, dtype=np.float64)
    cost = np.stack([_lambda_cost(m, k) for m in MEMORY_CONFIGS_MB])
    rank = jax_core.cost_ranks(cost)
    assert rank.shape == cost.shape and rank.dtype == np.int32
    c, r = cost.ravel(), rank.ravel()
    assert np.array_equal(r[:, None] == r[None, :], c[:, None] == c[None, :])
    assert np.array_equal(r[:, None] < r[None, :], c[:, None] < c[None, :])
    assert r.min() == 0 and (r[c == 0.0] == 0).all()   # the free edge's 0
    # 768 MB x 16 quanta = 1,024 MB x 12 in real arithmetic: float64 puts
    # 1,024 MB one ulp below, two-float holds both equal, the ranks differ
    i768, i1024 = (MEMORY_CONFIGS_MB.index(m) for m in (768, 1024))
    a, b = cost[i768, 16], cost[i1024, 12]
    assert b < a
    assert [x.tolist() for x in dfloat.split([a])] \
        == [x.tolist() for x in dfloat.split([b])]
    assert rank[i1024, 12] < rank[i768, 16]
    hi, lo = dfloat.split(c)
    merged = (hi[:, None] == hi[None, :]) & (lo[:, None] == lo[None, :])
    assert (merged & (r[:, None] != r[None, :])).any()


@pytest.mark.parametrize("branch", BRANCHES)
@pytest.mark.parametrize("tie", [(16, 12), (12, 9)],
                         ids=["768x16=1024x12", "768x12=1024x9"])
def test_mincost_picks_float64s_side_of_a_billing_tie(monkeypatch, branch,
                                                      tie):
    """Two configs whose bills tie in real arithmetic; the edge is out of
    the deadline. Float64 picks 1,024 MB in the first tie and 768 MB in the
    second, where 1,024 MB is the faster: a two-float cost compare sees a
    tie there and would fall through to latency. Every row is such a tie,
    and on the two-float branch ``cost_rank_splits`` counts each."""
    import dataclasses

    from repro.core.perf_models import RidgeModel

    q768, q1024 = tie
    a, b = _lambda_cost(768, q768), _lambda_cost(1024, q1024)
    assert a != b
    cheaper = "768" if a < b else "1024"
    twin, models = fit_app("FD", seed=0, n_inputs=40, configs=(768, 1024))
    # compute times inside the billed quanta: 768 MB left of the split,
    # 1,024 MB right of it
    comp = GBRT(config=GBRTConfig(n_trees=1, max_depth=1, learning_rate=1.0),
                features=np.array([[1]], np.int32),
                thresholds=np.array([[896.0]]),
                leaves=np.array([[q768 * 100.0 - 50.0,
                                  q1024 * 100.0 - 50.0]]))
    models = dataclasses.replace(
        models, comp_cloud=comp,
        comp_edge=RidgeModel(theta=np.array([1e6, 0.0])))
    tasks = _bursty(twin, 64)
    _steer(monkeypatch, branch)
    rt = _fd_runtime(twin, models, (768, 1024))
    res = rt.serve_stream(tasks, chunk_size=32, array_backend="jax")
    assert rt.engine.jax_stats is not None
    assert set(res.records.targets) == {cheaper}
    ref = _fd_runtime(twin, models, (768, 1024)).serve_stream(
        tasks, chunk_size=32)
    assert set(ref.records.targets) == {cheaper}
    splits = rt.stream_stats["spans"]["cost_rank_splits"]
    assert splits == (len(tasks) if branch == "two_float" else 0)


# ------------------------------------------------------- fallback regression
def test_hedged_policy_falls_back_to_numpy(ir_setup):
    twin, models = ir_setup
    tasks = _bursty(twin, 200)
    mk = lambda: HedgedPolicy(MinLatencyPolicy(c_max=6e-6, alpha=0.05),
                              hedge_threshold_ms=50.0)
    ref = _runtime(twin, models, policy=mk()).serve_stream(tasks,
                                                           chunk_size=64)
    rt = _runtime(twin, models, policy=mk())
    res = rt.serve_stream(tasks, chunk_size=64, array_backend="jax")
    assert_records_equal(res.records, ref.records)
    assert getattr(rt.engine, "jax_stats", None) is None  # numpy path ran


def test_out_of_order_stream_falls_back(ir_setup):
    twin, models = ir_setup
    tasks = _bursty(twin, 120)
    tasks[10], tasks[50] = tasks[50], tasks[10]
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=1000)
    rt = _runtime(twin, models)
    res = rt.serve_stream(tasks, chunk_size=1000, array_backend="jax")
    assert_records_equal(res.records, ref.records)
    assert getattr(rt.engine, "jax_stats", None) is None


def test_record_decisions_falls_back(ir_setup):
    twin, models = ir_setup
    pred = build_fleet_predictor(models, dict(FLEET3), configs=CONFIGS)
    eng = DecisionEngine(predictor=pred,
                         policy=MinLatencyPolicy(c_max=6e-6, alpha=0.05),
                         record_decisions=True, array_backend="jax")
    backend = TwinBackend(twin, seed=11, edge_names=tuple(FLEET3),
                          edge_speed=FLEET3)
    rt = PlacementRuntime(eng, backend)
    tasks = _bursty(twin, 80)
    res = rt.serve_stream(tasks, chunk_size=80)
    assert len(eng.decisions) == 80
    assert getattr(eng, "jax_stats", None) is None
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=80)
    assert_records_equal(res.records, ref.records)


# ----------------------------------------------------- backend plumbing
def test_array_backend_validation(ir_setup):
    twin, models = ir_setup
    pred = build_fleet_predictor(models, dict(FLEET3), configs=CONFIGS)
    with pytest.raises(ValueError, match="array_backend"):
        DecisionEngine(predictor=pred,
                       policy=MinLatencyPolicy(c_max=6e-6, alpha=0.05),
                       array_backend="cupy")
    rt = _runtime(twin, models)
    with pytest.raises(ValueError, match="array_backend"):
        rt.serve_stream(_bursty(twin, 4), array_backend="cupy")


def test_serve_stream_restores_engine_backend(ir_setup):
    twin, models = ir_setup
    rt = _runtime(twin, models)
    assert rt.engine.array_backend == "numpy"
    rt.serve_stream(_bursty(twin, 40), chunk_size=40,
                    array_backend="jax_interpret")
    assert rt.engine.array_backend == "numpy"


def test_core_cache_and_no_retrace(ir_setup):
    """One core per engine config, and the second same-shape chunk reuses
    every jit cache entry — the no-retrace guarantee the bench smoke checks."""
    twin, models = ir_setup
    rt = _runtime(twin, models)
    tasks = _bursty(twin, 384)
    # two warmup chunks: the first grows the container-pool cap (a real shape
    # change), the second compiles at the steady-state shapes
    rt.serve_stream(tasks[:256], chunk_size=128, array_backend="jax")
    core = jax_core.core_for(rt.engine)
    assert core is not None and core.valid_for(rt.engine)
    assert jax_core.core_for(rt.engine) is core  # cached, not rebuilt
    before = core.compile_stats()
    rt.serve_stream(tasks[256:], chunk_size=128, array_backend="jax")
    assert jax_core.core_for(rt.engine) is core
    assert core.compile_stats() == before  # steady shapes ⇒ no retrace


# ------------------------------------------------- GBRT jax operand cache
def test_predict_jax_operand_cache(rng):
    x = rng.uniform(0.0, 100.0, size=(200, 2))
    y = (x[:, 0] * 1.5 + np.sin(x[:, 1])) * 10.0
    m = GBRT.fit(x, y, GBRTConfig(n_trees=12, max_depth=3))
    np.testing.assert_allclose(np.asarray(m.predict_jax(x)), m.predict(x),
                               rtol=1e-6)
    ops1 = gbrt_mod._jax_operands(m)
    assert gbrt_mod._jax_operands(m) is ops1  # hosted once per identity
    # refit-by-swap: a fresh model must get fresh operands
    m2 = GBRT.fit(x, y * 2.0, GBRTConfig(n_trees=12, max_depth=3))
    ops2 = gbrt_mod._jax_operands(m2)
    assert ops2 is not ops1
    np.testing.assert_allclose(np.asarray(m2.predict_jax(x)), m2.predict(x),
                               rtol=1e-6)


# --------------------------------------------------- hypothesis property
def test_random_streams_keep_interpret_parity(ir_setup):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    twin, models = ir_setup

    @given(
        gaps=st.lists(st.floats(min_value=0.0, max_value=2000.0,
                                allow_nan=False), min_size=3, max_size=24),
        size_seed=st.integers(min_value=0, max_value=2**31 - 1),
        chunk=st.sampled_from([1, 5, 64]),
    )
    @settings(max_examples=15, deadline=None)
    def prop(gaps, size_seed, chunk):
        r = np.random.default_rng(size_seed)
        t = 0.0
        tasks = []
        for i, g in enumerate(gaps):
            t += g
            size, nbytes = twin.sample_input(r)
            tasks.append(TaskInput(idx=i, arrival_ms=t, size=size,
                                   bytes=nbytes))
        ref = _runtime(twin, models).serve_stream(tasks, chunk_size=chunk)
        res = _runtime(twin, models).serve_stream(
            tasks, chunk_size=chunk, array_backend="jax_interpret")
        assert_records_equal(res.records, ref.records)

    prop()


# ------------------------------------------------- stream residency (ISSUE 9)
@pytest.mark.parametrize("chunk_size,n", [(1, 60), (53, 300), (4096, 300)],
                         ids=["chunk1", "chunk53", "chunk4096"])
def test_resident_stream_parity_and_sync_counts(ir_setup, monkeypatch,
                                                chunk_size, n):
    """Cross-chunk device residency: per-record bit parity with the one-shot
    numpy oracle AND exactly ONE host materialization for the whole clean
    stream (the stream-end sync) — chunk boundaries stop being sync points."""
    monkeypatch.setattr(decision_mod, "COLUMNAR_CHUNK", 64)
    twin, models = ir_setup
    tasks = _bursty(twin, n)
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=chunk_size)
    rt = _runtime(twin, models)
    res = rt.serve_stream(tasks, chunk_size=chunk_size,
                          array_backend="jax_interpret")
    assert_records_equal(res.records, ref.records)
    r = rt.stream_stats["residency"]
    assert r["enabled"]
    assert r["resident_chunks"] == rt.stream_stats["chunks"]
    assert r["chunk_commits"] == 0
    assert r["state_syncs"] == 1 and r["fallback_syncs"] == 0
    if rt.stream_stats["chunks"] > 1:
        assert r["prefetched"] >= 1  # the transfer thread staged chunks


def test_resident_midstream_fallback_and_reentry(ir_setup):
    """A hedged chunk mid-stream exits residency through ONE fallback sync
    (host walk sees canonical state), and the following chunks re-enter
    residency with state intact — parity vs the numpy oracle under the same
    policy-swap schedule."""
    twin, models = ir_setup
    tasks = _bursty(twin, 300)

    def swapping_chunks(rt):
        # chunks 0-1 resident, chunk 2 hedged (host walk), chunks 3-4 resident
        orig = rt.engine.policy
        hedged = HedgedPolicy(MinLatencyPolicy(c_max=6e-6, alpha=0.05),
                              hedge_threshold_ms=50.0)
        for i in range(5):
            if i == 2:
                rt.engine.policy = hedged
            elif i == 3:
                rt.engine.policy = orig
            yield tasks[i * 60:(i + 1) * 60]

    ref_rt = _runtime(twin, models)
    ref = ref_rt.serve_stream(swapping_chunks(ref_rt), chunk_size=60)
    rt = _runtime(twin, models)
    # prefetch off: the transfer thread pulls chunk k+1 (firing the swap
    # side effect) while chunk k is still placing, which would reorder the
    # schedule this test pins down
    res = rt.serve_stream(swapping_chunks(rt), chunk_size=60,
                          array_backend="jax_interpret", prefetch=False)
    assert_records_equal(res.records, ref.records)
    core = jax_core.core_for(rt.engine)
    assert core is not None
    assert core.resident_chunks == 4
    assert core.fallback_syncs == 1    # the hedged chunk's exit
    assert core.state_syncs == 2       # fallback exit + stream end
    assert core.chunk_commits == 0


def test_resident_pool_growth_donation_safety(ir_setup):
    """Compiled mode donates the state seed into the jitted step; a resident
    chunk whose cold starts overflow the pool must restore the seed from the
    device-side backup, compact/grow, and re-run — no use-after-donate, and
    decisions stay identical to numpy."""
    twin, models = ir_setup
    tasks = _bursty(twin, 400)
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=64)
    rt = _runtime(twin, models)
    res = rt.serve_stream(tasks, chunk_size=64, array_backend="jax")
    ra, rb = ref.records, res.records
    assert list(ra.targets) == list(rb.targets)
    for col in ("predicted_cold", "actual_cold", "feasible"):
        assert np.array_equal(getattr(ra, col), getattr(rb, col)), col
    core = jax_core.core_for(rt.engine)
    assert core is not None
    assert core.resident_regrows >= 1  # the donated-seed retry path ran
    r = rt.stream_stats["residency"]
    assert r["chunk_commits"] == 0 and r["state_syncs"] == 1


def test_pool_width_starts_at_a_slot_per_chunk_rows(ir_setup, monkeypatch):
    """A stream's pools start at one slot per ``POOL_ROWS_PER_SLOT`` padded
    chunk rows, not at ``POOL_MIN_CAP``: a stream of large chunks then
    builds fewer place programs (one per width) on its way to the width its
    chunks' cold starts need. Decisions stay numpy's."""
    twin, models = ir_setup
    tasks = _bursty(twin, 4096)
    ref = _runtime(twin, models).serve_stream(tasks, chunk_size=2048)
    floor = 2048 // jax_core.POOL_ROWS_PER_SLOT
    assert floor == 2 * jax_core.POOL_MIN_CAP
    runs = []
    for per_slot in (jax_core.POOL_ROWS_PER_SLOT, 1 << 20):
        monkeypatch.setattr(jax_core, "POOL_ROWS_PER_SLOT", per_slot)
        rt = _runtime(twin, models)
        res = rt.serve_stream(tasks, chunk_size=2048, array_backend="jax")
        assert list(ref.records.targets) == list(res.records.targets)
        core = jax_core.core_for(rt.engine)
        runs.append((core.last_stats["pool_cap"],
                     core.compile_stats()["place"]))
    (cap, built), (cap_min, built_min) = runs
    assert cap == cap_min > floor
    assert built == built_min - 1     # no 8-slot program


def test_resident_state_syncs_for_external_place_many(ir_setup):
    """An out-of-stream ``place_many`` between two resident streams sees the
    canonical host state: stream 1's end sync landed it, and the standalone
    call commits per chunk like before residency existed."""
    twin, models = ir_setup
    tasks = _bursty(twin, 200)
    ref_rt = _runtime(twin, models)
    ref1 = ref_rt.serve_stream(tasks[:80], chunk_size=40)
    ref_mid = ref_rt.serve(tasks[80:120])
    ref2 = ref_rt.serve_stream(tasks[120:], chunk_size=40)
    rt = _runtime(twin, models)
    res1 = rt.serve_stream(tasks[:80], chunk_size=40,
                           array_backend="jax_interpret")
    rt.engine.array_backend = "jax_interpret"
    res_mid = rt.serve(tasks[80:120])
    rt.engine.array_backend = "numpy"
    res2 = rt.serve_stream(tasks[120:], chunk_size=40,
                           array_backend="jax_interpret")
    assert_records_equal(res1.records, ref1.records)
    assert_records_equal(res_mid.records, ref_mid.records)
    assert_records_equal(res2.records, ref2.records)
    core = jax_core.core_for(rt.engine)
    assert core.chunk_commits >= 1  # the standalone call committed host-side


# ------------------------------------------------- the chip's arithmetic
def _chip_smoke():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tpu_branch_decision_identity_past_2_24_ms(monkeypatch):
    """The TPU branch — two-float, "assoc" scans, the GBRT kernel (here in
    interpret mode) — steered on a CPU host, through the chip smoke test's
    own serving body: the paper's 19 configs, an IR stream whose arrivals
    pass 2**24 ms (where f32 spacing reaches 1-2 ms), decision-identical to
    the numpy oracle on every record, with every chunk resident."""
    monkeypatch.setattr(jax_core, "platform", lambda: "tpu")
    smoke = _chip_smoke()
    lines = []
    report = smoke.smoke(n_tasks=4096, chunk=1024, start_ms=1.7e7,
                         cont_chunks=1, log=lines.append)
    assert report["minlat"]["n"] == 4096
    assert report["minlat"]["mismatched"] == 0
    assert report["mincost"]["mismatched"] == 0
    assert report["minlat"]["residency"]["resident_chunks"] == 4
    for err in report["minlat"]["max_rel_err"].values():
        assert err < 1e-12
    assert lines  # the smoke's progress lines went to the given log


def test_core_refuses_to_hide_the_device(ir_setup, monkeypatch):
    """A core that cannot build for a non-semantic reason raises instead of
    serving on numpy: here the GBRT kernel asked for on an accelerator its
    Mosaic code cannot run on."""
    import jax

    from repro.core import predictor as predictor_mod

    twin, models = ir_setup
    monkeypatch.setattr(predictor_mod, "GBRT_KERNEL_MODE", "force")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    rt = _runtime(twin, models)
    with pytest.raises(RuntimeError, match="cannot run on a 'gpu'"):
        rt.serve_stream(_bursty(twin, 16), chunk_size=16,
                        array_backend="jax")


def test_compile_cache_location(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; without
    it the cache sits at the fixed ``<checkout>/.jax_cache``."""
    import jax

    from repro import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        where = compile_cache.configure()
        assert where == str(compile_cache.DEFAULT_DIR)
        assert where.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
