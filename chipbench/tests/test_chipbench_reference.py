"""The plain reference against the program's host path, and its control:
the reference in float32 put in the program's place must come out not
correct. Small streams that start a day in, as the benchmark's do."""

import json

import numpy as np
import pytest

import _paths  # noqa: F401
from _paths import BENCH
from harness import arrivals, compare, models, reference, sut

SEED = 3_000_000_019      # larger than 32 signed bits hold


def _setup(name, n=2048):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    tables = models.fit_deployment(cfg)
    st = arrivals.Stream(cfg["app_spec"], {"kind": "poisson",
                                           "rate_per_s": 4.0}, SEED)
    arr, size, nb = st.block(n)
    return cfg, tables, arr + cfg["stream_start_ms"], size, nb


@pytest.fixture(scope="module", params=["ir19-minlat0", "ir19-minlat", "fd19-mincost"])
def deployment(request):
    return _setup(request.param)


def test_reference_matches_program_host_path_exactly(deployment):
    cfg, tables, arr, size, nb = deployment
    rt, _ = sut.build(cfg, tables, SEED)
    chunks = [sut.task_chunk(lo, arr[lo:lo + 512], size[lo:lo + 512],
                             nb[lo:lo + 512]) for lo in range(0, len(arr), 512)]
    res = rt.serve_stream(iter(chunks), chunk_size=512,
                          array_backend="numpy")
    ref = reference.serve(cfg, tables, SEED, arr, size, nb)
    got = compare.readings(sut.records(res), ref,
                           reference.target_names(cfg))
    assert got == {"missing": 0, "decisions_differ": 0, "pred_rel_err": 0.0,
                   "outcome_cold_differ": 0, "outcome_rel_err": 0.0}
    ok, _ = compare.verdict(got)
    assert ok


def test_float32_control_is_not_correct(deployment):
    cfg, tables, arr, size, nb = deployment
    names = reference.target_names(cfg)
    ref = reference.serve(cfg, tables, SEED, arr, size, nb)
    ctl = reference.serve(cfg, tables, SEED, arr, size, nb, dtype=np.float32)
    got = compare.readings(compare.as_served(ctl, names), ref, names)
    ok, rows = compare.verdict(got)
    assert not ok, rows
    assert got["pred_rel_err"] > compare.LIMITS["pred_rel_err"]
