"""The GBRT kernel's work, counted from the model's shapes."""

import pytest

import _paths  # noqa: F401
from harness import layers
from harness.peaks import peaks


def test_gbrt_work_from_shapes():
    g = {"max_depth": 3, "n_trees": 150}
    ops, byts = layers.gbrt_work(16384, g, 19)
    assert ops == 16384 * 19 * 150 * (3 + 1)
    table = 150 * (7 * (4 + 8) + 8 * 8)
    assert byts == 16384 * 8 + 16384 * 19 * 8 + table


def test_gbrt_roofline_share_of_recorded_launches():
    class C:
        def __init__(self, n):
            self.n = n

    g = {"max_depth": 3, "n_trees": 150}
    ctx = {"trace": {"kernel_calls": [0, 1], "kernel_s": 1e-3},
           "device": {"kind": "TPU v5 lite"},
           "cfg": {"memory_configs_mb": list(range(19))},
           "tables": {"gbrt": g}, "chunks": [C(1), C(1000), C(2000)],
           "warm": 1}
    ops = sum(layers.gbrt_work(n, g, 19)[0] for n in (1000, 2000))
    byts = sum(layers.gbrt_work(n, g, 19)[1] for n in (1000, 2000))
    pk = peaks("TPU v5 lite")
    want = 100 * max(ops / pk["flops"], byts / pk["hbm_bw"]) / 1e-3
    assert layers.gbrt_roofline(ctx) == pytest.approx(want)
    ctx["trace"]["kernel_calls"] = []
    assert layers.gbrt_roofline(ctx) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        peaks("cpu")
