"""The cells that the FD MinCost deployment and the bursty IR mix bring:
whole runs on the CPU at a small chunk size come out correct, and the
reader of the program's ``cost_rank_splits`` counter reads the window's
count, or nothing (no error) from a program that does not count it."""

import pytest

import _paths  # noqa: F401
from _paths import ROOT
from harness import spec, sut
from harness.sut import Chunk
from test_chipbench_faults import small_run  # noqa: F401 - the fixture

NAME = "cost_rank_splits_per_ktask.fd"


@pytest.fixture
def backends(monkeypatch):
    """The runtimes' backends a run builds, in order: the timed path's
    first, then the host-path baseline's."""
    built, orig = [], sut.build

    def build(*a, **k):
        rt, backend = orig(*a, **k)
        built.append(backend)
        return rt, backend
    monkeypatch.setattr(sut, "build", build)
    return built


@pytest.mark.parametrize("cell,branch", [
    ("fd19-mincost.replay", "float64"),
    ("fd19-mincost.replay", "two_float"),
    ("ir19-minlat0.bursty", "float64"),
])
def test_new_cell_runs_correct(small_run, backends, monkeypatch,  # noqa: F811
                               cell, branch):
    """On "two_float" the program's TPU branch runs on this CPU host."""
    if branch == "two_float":
        from repro.core import jax_core

        monkeypatch.setattr(jax_core, "platform", lambda: "tpu")
    out = small_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"replay_rate", "setup_s"}
    chunks = backends[0].chunks
    # every chunk in arrival order, so the device core placed each
    assert all("passes" in c.stats for c in chunks)
    ctx = {"chunks": chunks, "window_chunks": list(range(2, len(chunks)))}
    read = spec.reader(ROOT, NAME)(ctx)
    if cell.startswith("fd19"):
        assert isinstance(read, float)
        assert (read > 0) if branch == "two_float" else (read == 0)


def test_bursty_window_stream_continues_in_arrival_order():
    """The bursty mix's ``drive`` starts a window stream's phase clock where
    the stream starts; a stream from 0 draws exactly as before."""
    import importlib.util

    import numpy as np

    from harness import arrivals

    path = ROOT / "chipbench" / "traffic" / "replay_mmpp8.py"
    sp = importlib.util.spec_from_file_location("replay_mmpp8_mix", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    app = spec.config(ROOT, spec.load(ROOT), "ir19-minlat0")["app_spec"]
    proc = dict(spec.traffic(ROOT, "replay_mmpp8")["process"],
                rate_per_s=4.0)
    start = 1.9e6
    arr = mod._Stream(app, proc, 7, start_ms=start).block(4096)[0]
    assert arr[0] > start and (np.diff(arr) >= 0).all()
    for a, b in zip(mod._Stream(app, proc, 7).block(512),
                    arrivals.Stream(app, proc, 7).block(512)):
        assert np.array_equal(a, b)


def test_reader_reads_the_window_per_1000_tasks():
    totals = [0, 7, 7, 10, 12]
    chunks = [Chunk(k, k + 0.5, 500, {"cost_rank_splits": v})
              for k, v in enumerate(totals)]
    ctx = {"chunks": chunks, "window_chunks": [2, 3, 4]}
    assert spec.reader(ROOT, NAME)(ctx) == pytest.approx(5 / 1.5)
    ctx["chunks"] = [Chunk(k, k + 0.5, 500, {}) for k in range(5)]
    assert spec.reader(ROOT, NAME)(ctx) is None


def test_entries_of_the_new_cells():
    bench = spec.load(ROOT)
    m = [p for p in bench["per_layer"] if p["name"] == NAME]
    assert len(m) == 1 and m[0]["workloads"] == ["fd19-mincost.replay"]
    assert m[0]["moves"] == "replay_rate"
    mix = spec.traffic(ROOT, "replay_mmpp8")
    assert mix["kind"] == "replay" and mix["chunk_rows"] == 16384
    assert mix["process"] == {"kind": "mmpp", "rate_per_s": None,
                              "burst_multiplier": 8.0, "mean_quiet_s": 20.0,
                              "mean_burst_s": 5.0}
    assert mix["warm_seed"] == 0 and spec.traffic_driver(ROOT, "replay_mmpp8")
    assert spec.cell(bench, "fd19-mincost.replay")["traffic"] == "replay"
    for cell in ("fd19-mincost.replay", "ir19-minlat0.bursty"):
        assert spec.cell(bench, cell)["chips"] == 1
        assert {x["name"] for x in spec.e2e_of(bench, cell)} \
            == {"replay_rate", "setup_s"}
