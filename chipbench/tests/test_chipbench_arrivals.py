"""The task streams the traffic files describe."""

import json

import numpy as np

import _paths  # noqa: F401
from _paths import BENCH
from harness.arrivals import Stream

SPEC = json.loads((BENCH / "configs" / "ir19-minlat.json").read_text())[
    "app_spec"]


def test_same_seed_same_stream_and_poisson_rate():
    a = Stream(SPEC, {"kind": "poisson", "rate_per_s": 4.0}, 2 ** 31 + 7)
    b = Stream(SPEC, {"kind": "poisson", "rate_per_s": 4.0}, 2 ** 31 + 7)
    x = [a.block(4096), a.block(4096)]
    y = [b.block(4096), b.block(4096)]
    for u, v in zip(x, y):
        for p, q in zip(u, v):
            np.testing.assert_array_equal(p, q)
    arr = np.concatenate([x[0][0], x[1][0]])
    assert np.all(np.diff(arr) > 0) and x[1][0][0] > x[0][0][-1]
    assert abs(len(arr) / (arr[-1] / 1e3) - 4.0) < 0.2
    size, nbytes = x[0][1], x[0][2]
    assert size.min() >= 1.9e6 and size.max() <= 2.9e6
    np.testing.assert_allclose(nbytes, size * 0.35)


def test_mmpp_bursts_raise_the_rate():
    p = {"kind": "mmpp", "rate_per_s": 10.0, "burst_multiplier": 8.0,
         "mean_quiet_s": 20.0, "mean_burst_s": 5.0}
    arr, _, _ = Stream(SPEC, p, 5).block(20000)
    assert np.all(np.diff(arr) > 0)
    # the mean rate of an MMPP with these phases: (20*10 + 5*80) / 25 = 24/s
    rate = len(arr) / (arr[-1] / 1e3)
    assert 18.0 < rate < 30.0
    gaps = np.diff(arr)
    assert np.percentile(gaps, 10) < 1000.0 / 10.0 / 4


def test_fixed_warm_up_then_the_runs_own_window():
    from harness.arrivals import warm_and_window

    p = {"kind": "poisson", "rate_per_s": 4.0}
    w1, s1 = warm_and_window(SPEC, p, 11, [64, 64], [8.0, 1.0], 0)
    w2, s2 = warm_and_window(SPEC, p, 12, [64, 64], [8.0, 1.0], 0)
    for a, b in zip(w1, w2):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    # the first warm block is eight times as dense as the second
    assert np.diff(w1[0][0]).mean() < np.diff(w1[1][0]).mean() / 3
    a1, a2 = s1.block(64)[0], s2.block(64)[0]
    assert a1[0] > w1[1][0][-1] and a2[0] > w1[1][0][-1]
    assert not np.array_equal(a1, a2)
    w3, s3 = warm_and_window(SPEC, p, 11, [64], [1.0], None)
    assert not np.array_equal(w3[0][0], w1[0][0][:64])
