"""The trace reduction: on hand-made events, and on a small trace recorded
on a TPU v5 lite (``data/fd_small.xplane.pb.gz``: the fd19-mincost replay at
256-task chunks, the window's first chunk traced)."""

from pathlib import Path

import pytest

import _paths  # noqa: F401
from harness import trace

DATA = Path(__file__).resolve().parent / "data" / "fd_small.xplane.pb.gz"


def _events():
    k = ("%gbrt_predict_multi.1 = f32[2,19,128,128] custom-call(f32[2,128,128]"
         " %x), custom_call_target=\"tpu_custom_call\"")
    return {"devices": {"/device:TPU:0": {
        "modules": [("jit_predict(11)", 0, 10), ("jit_place(22)", 12, 88),
                    ("jit_predict(11)", 150, 10), ("jit_place(22)", 161, 39)],
        "ops": [("%fusion.1 = f32[8] fusion()", 0, 2), (k, 2, 6),
                ("%while.3 = (s32[]) while()", 12, 80),
                (k, 152, 5), ("%while.3 = (s32[]) while()", 161, 30)]}},
        "host": [("chipbench.twin", 100, 45), ("np.asarray(jax.Array)", 12, 90),
                 ("PjitFunction(place)", 158, 2)]}


def test_union_and_gaps():
    import numpy as np

    iv = np.array([[0, 10], [5, 20], [30, 40], [40, 45]], float)
    assert trace.union_length(iv) == 35
    assert trace.gaps(iv) == [(20, 30)]
    assert trace.union_length(np.empty((0, 2))) == 0.0


def test_reduce_events_by_hand():
    red = trace.reduce_events(_events(), window_s=250e-9)
    assert red["busy_s"] == pytest.approx(147e-9)
    assert red["programs"] == pytest.approx({"predict": 20e-9,
                                             "place": 127e-9})
    assert red["kernel_s"] == pytest.approx(11e-9)
    assert red["kernel_calls"] == [0, 1]
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["chipbench.twin", pytest.approx(50e-9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert red["breakdown"]["device_ops"][0][0] == "place"


def test_kernel_counted_only_while_ops_were_recorded():
    ev = _events()
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    del ops[3:]          # the buffer filled before the second predict
    red = trace.reduce_events(ev, window_s=250e-9)
    assert red["kernel_calls"] == [0]
    assert red["kernel_s"] == pytest.approx(6e-9)


def test_program_name():
    assert trace.program_name("jit_place(131628345365220270)") == "place"
    assert trace.program_name("jit__pad(5)") == "_pad"


def test_recorded_chip_trace():
    red = trace.reduce_events(trace.load(DATA), window_s=1.0)
    # as the run that recorded it reduced it on the chip
    assert red["busy_s"] == pytest.approx(0.024828809)
    assert red["programs"]["place"] == pytest.approx(0.024511247)
    assert red["kernel_s"] == pytest.approx(0.000226382)
    assert {"place", "predict"} <= set(red["programs"])
    assert 0 < red["busy_s"] < 1.0
    assert red["kernel_calls"] and red["kernel_s"] > 0
    assert red["kernel_s"] < red["programs"]["predict"]
    assert len(red["breakdown"]["idle_gaps"]) <= 10
