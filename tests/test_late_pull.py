"""When a device-backed ``serve_stream`` pulls its next chunk.

On the CPU, compiled ``array_backend="jax"`` unless a test says otherwise,
with the small IR deployment of ``tests/test_jax_core.py``:

- after a short chunk (a caught-up source) the transfer thread pulls the
  next chunk only once the loop has decided the chunk and hands it to the
  backend; after a full chunk it pulls at once, while the chunk is placed;
- ``late_pulls`` counts the chunks whose pull was held: the short chunks
  but the last;
- the records are those of ``prefetch=False`` and of the numpy path;
- a backend that raises under a short-chunk source ends the stream with
  its exception, and the transfer thread is joined.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.fit import fit_app
from test_jax_core import (CONFIGS, FLOAT_COLS, _bursty, _runtime,
                           assert_records_equal)
from test_stream_spans import CHUNK, _serve

SHORT = CHUNK // 4


@pytest.fixture(scope="module")
def ir_setup():
    return fit_app("IR", seed=0, n_inputs=120, configs=CONFIGS)


@pytest.fixture(scope="module")
def warm_rt(ir_setup):
    """A runtime whose core has compiled the full and the short chunk."""
    twin, models = ir_setup
    rt = _runtime(twin, models)
    tasks = _bursty(twin, 3 * CHUNK)
    rt.serve_stream(Source(tasks, [CHUNK, SHORT, CHUNK // 2, SHORT]),
                    chunk_size=CHUNK, array_backend="jax")
    return rt


class Source:
    """The chunks of ``tasks`` cut at ``sizes``; ``pulls`` holds when each
    chunk's ``next`` was entered."""

    def __init__(self, tasks, sizes):
        self.tasks, self.sizes = tasks, sizes
        self.pulls: list[float] = []

    def __iter__(self):
        lo = 0
        for n in self.sizes:
            self.pulls.append(time.perf_counter())
            yield self.tasks[lo:lo + n]
            lo += n


def _timed_place(rt, monkeypatch, sleep_s):
    """Wraps the engine's ``place_many``: it sleeps ``sleep_s`` first (a long
    placement), and the returned list gets the time each call returned."""
    done: list[float] = []
    orig = rt.engine.place_many

    def place_many(*a, **k):
        time.sleep(sleep_s)
        out = orig(*a, **k)
        done.append(time.perf_counter())
        return out

    monkeypatch.setattr(rt.engine, "place_many", place_many)
    return done


@pytest.mark.parametrize("step", [SHORT, CHUNK], ids=["short", "full"])
def test_the_pull_follows_the_fill_of_the_chunk_before(warm_rt, ir_setup,
                                                       monkeypatch, step):
    """A short chunk's successor is pulled after the short chunk was decided
    (its backend call comes next, on the loop's thread); a full chunk's
    successor is pulled while the full chunk is still being placed."""
    twin, _ = ir_setup
    n = 6
    src = Source(_bursty(twin, n * step, seed=61), [step] * n)
    decided = _timed_place(warm_rt, monkeypatch, sleep_s=0.02)
    calls = _serve(warm_rt, src, monkeypatch)
    assert len(src.pulls) == len(decided) == len(calls) == n
    nxt, dec = np.array(src.pulls[1:]), np.array(decided[:-1])
    entered = np.array([t for t, _ in calls])
    assert np.all(nxt < entered[1:])          # pulled before it is served
    r = warm_rt.stream_stats["residency"]
    assert r["prefetched"] == n
    if step < CHUNK:
        assert np.all(nxt > dec), nxt - dec
        assert r["late_pulls"] == n - 1
    else:
        assert np.all(nxt < dec), nxt - dec
        assert np.all(nxt < entered[:-1])
        assert r["late_pulls"] == 0


def test_late_pulls_count_the_short_chunks_but_the_last(warm_rt, ir_setup,
                                                        monkeypatch):
    twin, _ = ir_setup
    sizes = [SHORT, CHUNK, SHORT, CHUNK // 2, SHORT]
    src = Source(_bursty(twin, sum(sizes), seed=67), sizes)
    calls = _serve(warm_rt, src, monkeypatch)
    # read at each backend call: chunks 1, 3 and 4 follow a short chunk
    assert [s["late_pulls"] for _, s in calls] == [0, 1, 1, 2, 3]
    st = warm_rt.stream_stats
    assert st["residency"]["late_pulls"] == st["spans"]["late_pulls"] == 3


@pytest.mark.parametrize("backend", ["jax", "jax_interpret"])
def test_held_pulls_change_no_record(ir_setup, backend):
    """Prefetch with held pulls, prefetch off and the numpy oracle serve the
    same records: bit for bit where the backend's contract is bit parity
    (prefetch on or off; interpret mode against numpy), and the compiled
    core's decisions with floats to 1e-9 against numpy."""
    twin, models = ir_setup
    sizes = [SHORT, SHORT, CHUNK, SHORT, CHUNK // 2, SHORT, SHORT]
    tasks = _bursty(twin, sum(sizes), seed=71)

    def serve(array_backend, **kw):
        rt = _runtime(twin, models)
        res = rt.serve_stream(Source(tasks, sizes), chunk_size=CHUNK,
                              array_backend=array_backend, **kw)
        return rt.stream_stats, res.records

    st, held = serve(backend)
    assert st["residency"]["late_pulls"] == 5
    st_off, off = serve(backend, prefetch=False)
    assert st_off["residency"]["late_pulls"] == 0
    assert st_off["spans"]["late_pulls"] == 0
    assert_records_equal(held, off)
    _, ref = serve("numpy")
    if backend == "jax_interpret":
        assert_records_equal(held, ref)
    else:
        assert list(held.targets) == list(ref.targets)
        for col in ("predicted_cold", "actual_cold", "feasible", "hedged"):
            assert np.array_equal(getattr(held, col), getattr(ref, col)), col
        for col in FLOAT_COLS:
            np.testing.assert_allclose(
                getattr(held, col).astype(float),
                getattr(ref, col).astype(float),
                rtol=1e-9, atol=1e-12, err_msg=col)


def test_backend_error_under_short_chunks_ends_the_stream(ir_setup):
    twin, models = ir_setup
    rt = _runtime(twin, models)
    sizes = [SHORT] * 8
    src = Source(_bursty(twin, sum(sizes), seed=73), sizes)
    orig = rt.backend.execute_many
    calls: list[int] = []

    def execute_many(tasks, targets):
        calls.append(len(tasks))
        if len(calls) == 3:
            raise RuntimeError("backend down")
        return orig(tasks, targets)

    rt.backend.execute_many = execute_many
    out: dict = {}

    def run():
        try:
            rt.serve_stream(src, chunk_size=CHUNK, array_backend="jax")
        except RuntimeError as e:
            out["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(timeout=120.0)
    assert not th.is_alive(), "the stream hung after its backend raised"
    assert str(out["err"]) == "backend down"
    # chunks 0-2, and chunk 3, whose pull the failing call released; the
    # stream joined that pull before it returned, and pulled nothing more
    assert len(src.pulls) == 4
