"""A whole run past the harness's look for a chip, on the CPU at a small
chunk size: sound, it comes out correct; with the timed path broken
underneath, it comes out not correct."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import _paths  # noqa: F401
from _paths import ROOT
from harness import driver, models, spec

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
_FITS = {}


def _cells(kind):
    bench = spec.load(ROOT)
    return [w["name"] for w in bench["workloads"]
            if spec.traffic(ROOT, w["traffic"])["kind"] == kind]


@pytest.fixture
def small_run(monkeypatch):
    """``run(cell)``: a one-second window of 256-task chunks."""
    orig_traffic, orig_fit = spec.traffic, models.fit_deployment

    def traffic(root, name):
        t = orig_traffic(root, name)
        if t["kind"] == "replay":
            t["chunk_rows"] = 256
        else:
            t["warm_rows"] = [64, 32, 16, 8]
            t["process"]["rate_per_s"] = 100.0
        return t

    def fit(cfg):
        if cfg["name"] not in _FITS:
            _FITS[cfg["name"]] = orig_fit(cfg)
        return _FITS[cfg["name"]]

    monkeypatch.setattr(spec, "traffic", traffic)
    monkeypatch.setattr(models, "fit_deployment", fit)

    def run(cell=None):
        cell = cell or _cells("replay")[0]
        return driver.run(ROOT, cell, 2_147_483_659, 1, False,
                          time.perf_counter(), DEVICE)
    return run


def test_sound_run_is_correct(small_run):
    out = small_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["checks"]) == list(out["checks"])  # keys kept in order
    assert set(out["metrics"]) == {"replay_rate", "setup_s"}


def _patch_decisions(monkeypatch, edit):
    from repro.core import jax_core

    orig = jax_core.JaxPlacementCore.place_chunk

    def place_chunk(self, engine, tasks, edge_queues, interpret):
        out = orig(self, engine, tasks, edge_queues, interpret)
        edit(self, out)
        return out
    monkeypatch.setattr(jax_core.JaxPlacementCore, "place_chunk",
                        place_chunk)


def test_altered_decision_is_not_correct(small_run, monkeypatch):
    def edit(core, out):
        out.target_codes[len(out) // 2] = \
            (out.target_codes[len(out) // 2] + 1) % len(out.names)
    _patch_decisions(monkeypatch, edit)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["decisions_differ"]["value"] > 0


def test_state_left_unchanged_between_chunks_is_not_correct(small_run,
                                                             monkeypatch):
    from repro.core import jax_core

    orig = jax_core.JaxPlacementCore.place_chunk

    def place_chunk(self, engine, tasks, edge_queues, interpret):
        self._resident = None   # every chunk seeded from the initial state
        return orig(self, engine, tasks, edge_queues, interpret)
    monkeypatch.setattr(jax_core.JaxPlacementCore, "place_chunk",
                        place_chunk)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["decisions_differ"]["value"] > 0


def test_half_of_each_chunk_left_out_is_not_correct(small_run, monkeypatch):
    from harness import sut

    orig = sut.serve

    def serve(rt, chunks, rows):
        return orig(rt, (c[:max(1, len(c) // 2)] for c in chunks), rows)
    monkeypatch.setattr(sut, "serve", serve)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["missing"]["value"] > 0


def test_altered_outcome_is_not_correct(small_run, monkeypatch):
    from repro.core.runtime import TwinBackend

    orig = TwinBackend.execute_many

    def execute_many(self, tasks, targets):
        out = orig(self, tasks, targets)
        out.latency_ms[0] += 1.0
        return out
    monkeypatch.setattr(TwinBackend, "execute_many", execute_many)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["outcome_rel_err"]["value"] > \
        out["checks"]["outcome_rel_err"]["limit"]


def test_open_loop_run_is_correct_and_reports_tails(small_run):
    """The open-loop cell, at a small size."""
    out = small_run(_cells("open_loop")[0])
    assert out["correct"], out["checks"]
    assert {"decision_p50_ms", "decision_p95_ms"} <= set(out["metrics"])
    p50 = out["metrics"]["decision_p50_ms"]["value"]
    assert 0 < p50 <= out["metrics"]["decision_p95_ms"]["value"]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         _cells("replay")[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
