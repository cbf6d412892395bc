"""Per-chunk spans and counters of a device-backed ``serve_stream``.

On the CPU, compiled ``array_backend="jax"``, with the small IR deployment
of ``tests/test_jax_core.py``:

- every chunk the core places publishes every span and counter total in
  ``engine.jax_stats`` at its backend call, and the totals only grow;
- the consumer spans tile the time between consecutive backend calls;
- a slow backend shows as ``ready_wait`` (the staged chunk waits for the
  loop), a slow producer as ``fetch_wait`` (the loop waits for the chunk);
- the spans of one chunk share its sequence number in the profiler;
- ``twin_slots`` counts the pool walk's slot visits by hand;
- ``stream_stats["spans"]`` holds the last record's totals, and
  ``stream_stats["residency"]`` the regrows;
- the numpy path, and a chunk the core does not place, leave no
  ``jax_stats``; ``compile_stats`` names every program.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import jax_core
from repro.core.decision import HedgedPolicy, MinLatencyPolicy
from repro.core.fit import fit_app
from repro.core.runtime import GTContainer, TwinBackend
from repro.core.spans import CONSUMER, OFF_LOOP
from repro.core.workload import TaskInput
from test_jax_core import CONFIGS, _bursty, _runtime

COUNTERS = ("d2h_reads", "twin_slots", "resident_regrows", "late_pulls",
            "cost_rank_splits")
FIELDS = CONSUMER + OFF_LOOP + COUNTERS
CHUNK = 32


@pytest.fixture(scope="module")
def ir_setup():
    return fit_app("IR", seed=0, n_inputs=120, configs=CONFIGS)


class Recorder:
    """Wraps a runtime's ``execute_many``: at each call, the time it was
    entered and a copy of ``engine.jax_stats``; an optional sleep."""

    def __init__(self, rt, sleep_s: float = 0.0):
        self.rt, self.sleep_s = rt, sleep_s
        self.orig = rt.backend.execute_many
        self.calls: list[tuple[float, dict | None]] = []

    def __call__(self, tasks, targets):
        js = getattr(self.rt.engine, "jax_stats", None)
        self.calls.append((time.perf_counter(),
                           dict(js) if js is not None else None))
        if self.sleep_s:
            time.sleep(self.sleep_s)
        return self.orig(tasks, targets)


def _serve(rt, workload, monkeypatch, sleep_s=0.0):
    rec = Recorder(rt, sleep_s)
    monkeypatch.setattr(rt.backend, "execute_many", rec)
    rt.serve_stream(workload, chunk_size=CHUNK, array_backend="jax")
    return rec.calls


def _deltas(calls, keys):
    """Per backend call after the first: the growth of ``keys``' sum."""
    tot = [sum(s[k] for k in keys) for _, s in calls]
    return np.diff(tot)


@pytest.fixture(scope="module")
def warm_rt(ir_setup):
    """A runtime whose core has compiled every shape the tests use."""
    twin, models = ir_setup
    rt = _runtime(twin, models)
    rt.serve_stream(_bursty(twin, 400), chunk_size=CHUNK,
                    array_backend="jax")
    return rt


@pytest.fixture(scope="module")
def stream(ir_setup):
    """One recorded stream from a fresh runtime (its pools grow)."""
    twin, models = ir_setup
    rt = _runtime(twin, models)
    mp = pytest.MonkeyPatch()
    try:
        calls = _serve(rt, _bursty(twin, 400), mp)
    finally:
        mp.undo()
    return rt, calls


def test_every_device_chunk_publishes_monotone_totals(stream):
    rt, calls = stream
    assert len(calls) == rt.stream_stats["chunks"] > 3
    for _, s in calls:
        assert s is not None and set(FIELDS) <= set(s)
    for k in FIELDS:
        vals = [s[k] for _, s in calls]
        assert all(b >= a for a, b in zip(vals, vals[1:])), k
    assert calls[-1][1]["d2h_reads"] > calls[0][1]["d2h_reads"] > 0
    assert calls[-1][1]["twin_slots"] > 0
    assert calls[-1][1]["predict"] > 0 and calls[-1][1]["place"] > 0


def test_consumer_spans_tile_the_cycle_between_backend_calls(stream):
    _, calls = stream
    cycles = np.diff([t for t, _ in calls])
    spans = _deltas(calls, CONSUMER)
    assert np.all(np.abs(cycles - spans) <= 0.05 * cycles + 1e-3), \
        (cycles, spans)


def test_stream_stats_spans_are_the_last_record(stream):
    rt, calls = stream
    tot = rt.stream_stats["spans"]
    assert set(tot) == set(FIELDS)
    assert tot == {k: rt.engine.jax_stats[k] for k in FIELDS}
    for k in FIELDS:
        assert tot[k] >= calls[-1][1][k]
    # the last chunk's execute span closed after its record was read
    assert tot["execute"] > calls[-1][1]["execute"]
    regrows = rt.stream_stats["residency"]["resident_regrows"]
    assert regrows == tot["resident_regrows"] >= 1   # the pools grew
    assert regrows == jax_core.core_for(rt.engine).resident_regrows


def test_slow_backend_shows_as_ready_wait(warm_rt, ir_setup, monkeypatch):
    twin, _ = ir_setup
    calls = _serve(warm_rt, _bursty(twin, 12 * CHUNK, seed=41), monkeypatch,
                   sleep_s=0.02)
    ready = _deltas(calls, ("ready_wait",))[1:]
    fetch = _deltas(calls, ("fetch_wait",))[1:]
    assert np.median(ready) >= 0.010, ready
    assert np.median(fetch) < 0.005, fetch


def test_slow_producer_shows_as_fetch_wait(warm_rt, ir_setup, monkeypatch):
    twin, _ = ir_setup
    tasks = _bursty(twin, 12 * CHUNK, seed=43)

    def chunks():
        for lo in range(0, len(tasks), CHUNK):
            time.sleep(0.05)
            yield tasks[lo:lo + CHUNK]

    calls = _serve(warm_rt, chunks(), monkeypatch)
    ready = _deltas(calls, ("ready_wait",))[1:]
    fetch = _deltas(calls, ("fetch_wait",))[1:]
    assert np.median(fetch) >= 0.010, fetch
    assert np.median(ready) < 0.005, ready


def test_spans_of_a_chunk_share_its_sequence_number(warm_rt, ir_setup,
                                                    monkeypatch):
    import jax.profiler

    events: list[tuple[str, int]] = []

    class Annotation:
        def __init__(self, name, chunk):
            self.ev = (name, chunk)

        def __enter__(self):
            events.append(self.ev)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    twin, _ = ir_setup
    warm_rt.serve_stream(_bursty(twin, 4 * CHUNK, seed=47),
                         chunk_size=CHUNK, array_backend="jax")
    n = warm_rt.stream_stats["chunks"]
    consumer = [e for e in events if e[0] != "stage"]
    want = [(s, k) for k in range(n) for s in CONSUMER] + [("fetch_wait", n)]
    assert consumer == want
    staged = [k for s, k in events if s == "stage"]
    assert staged == list(range(n)) and "ready_wait" not in dict(events)


def test_twin_slots_by_hand_on_a_three_container_pool(ir_setup):
    twin, _ = ir_setup
    busy = GTContainer(1e12, 0.0, 2e12)     # never idle
    tasks = [TaskInput(idx=i, arrival_ms=1000.0 + i, size=2.4e6,
                       bytes=8.4e5) for i in range(2)]

    b = TwinBackend(twin, seed=3, edge_names=())
    b.gt_cloud.pools["1536"] = [busy, busy, busy]
    out = b.execute_many(tasks, ["1536", "1536"])
    assert out.cold.all()
    # scans of 3 and 4 slots, then the rebuilt list of 5
    assert b.twin_slots == 3 + 4 + 5

    b = TwinBackend(twin, seed=3, edge_names=())
    b.gt_cloud.pools["1536"] = [busy, GTContainer(0.0, 0.0, 10.0), busy]
    b.execute_many(tasks[:1], ["1536"])
    # a scan of 3 finds the expired idle container, the reap's rebuild
    # walks the 3 again and keeps 2, the cold start makes 3, rebuilt once
    assert len(b.gt_cloud.pools["1536"]) == 3
    assert b.twin_slots == 3 + 3 + 3


def test_numpy_path_leaves_jax_stats_unset(ir_setup):
    twin, models = ir_setup
    rt = _runtime(twin, models)
    rt.serve_stream(_bursty(twin, 100), chunk_size=CHUNK)
    assert getattr(rt.engine, "jax_stats", None) is None
    assert "spans" not in rt.stream_stats
    assert rt.backend.twin_slots > 0


def test_chunk_the_core_does_not_place_leaves_no_record(ir_setup,
                                                         monkeypatch):
    """A hedged chunk mid-stream runs on numpy: its backend call sees no
    ``jax_stats`` (not the chunk before's, whose ``passes`` it would count
    again), and the chunks after it publish again."""
    twin, models = ir_setup
    rt = _runtime(twin, models)
    tasks = _bursty(twin, 5 * CHUNK, seed=53)
    orig = rt.engine.policy
    hedged = HedgedPolicy(MinLatencyPolicy(c_max=6e-6, alpha=0.05),
                          hedge_threshold_ms=50.0)

    def chunks():
        for i in range(5):
            rt.engine.policy = hedged if i == 2 else orig
            yield tasks[i * CHUNK:(i + 1) * CHUNK]

    rec = Recorder(rt)
    monkeypatch.setattr(rt.backend, "execute_many", rec)
    rt.serve_stream(chunks(), chunk_size=CHUNK, array_backend="jax",
                    prefetch=False)
    got = [s is not None for _, s in rec.calls]
    assert got == [True, True, False, True, True]
    assert rt.stream_stats["residency"]["fallback_chunks"] == 1


def test_compile_stats_names_every_program(warm_rt):
    stats = jax_core.core_for(warm_rt.engine).compile_stats()
    assert set(stats) == {"predict", "place", "state", "choose", "finalize",
                          "compact", "shift"}
    assert stats["predict"] >= 1 and stats["place"] >= 1
