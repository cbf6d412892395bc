"""The replay window's end rule and the open-loop latency arithmetic."""

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import window as win


def test_replay_window_closes_on_first_chunk_at_or_after_seconds():
    # warm-up ends at 10.0; chunks end at 12, 14, 15.5, 16 -> window of 5 s
    # closes on the chunk ending at 15.5 (the first at or after 15.0)
    t_out = [4.0, 10.0, 12.0, 14.0, 15.5, 16.0]
    n = [100, 100, 10, 20, 30, 40]
    assert win.replay_close(t_out, 2, 5.0) == 4
    rate, tasks, span = win.replay_rate(t_out, n, 2, 5.0)
    assert tasks == 60 and span == pytest.approx(5.5)
    assert rate == pytest.approx(60 / 5.5)


def test_replay_window_open_until_a_chunk_ends_past_seconds():
    assert win.replay_close([1.0, 2.0, 2.5], 1, 5.0) is None
    assert win.replay_close([1.0], 2, 5.0) is None
    assert win.replay_close([1.0, 6.0], 1, 5.0) == 1   # exactly at the edge
    with pytest.raises(ValueError):
        win.replay_rate([1.0, 2.0], [5, 5], 1, 5.0)


def test_decision_latency_counts_undecided_at_drain_end():
    due = np.array([0.0, 0.5, 1.0, 1.5])
    decided = np.array([0.2, 0.6, np.nan, 1.75])
    lat = win.decision_latency_ms(due, decided, drain_end=3.0)
    np.testing.assert_allclose(lat, [200.0, 100.0, 2000.0, 250.0])


def test_percentile_is_over_all_tasks():
    x = np.arange(1, 101, dtype=float)       # 1..100
    assert win.percentile(x, 50) == pytest.approx(50.5)
    assert win.percentile(x, 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        win.percentile(np.array([]), 95)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_open_loop_releases_due_tasks_in_batches_and_stops():
    clk = FakeClock()
    loop = win.OpenLoop(np.array([0.1, 0.2, 0.25, 1.0, 1.1]), max_batch=2,
                        drain_s=5.0, clock=clk, sleep=clk.sleep)
    loop.start(10.0)
    clk.t = 10.0
    assert loop.next_batch() == (0, 1)       # waits until 10.1
    assert clk.t == pytest.approx(10.1)
    clk.t = 10.3                             # 0.2 and 0.25 are due
    assert loop.next_batch() == (1, 3)
    assert loop.next_batch() == (3, 4)       # waits until 11.0
    clk.t = 11.5
    assert loop.next_batch() == (4, 5)
    assert loop.next_batch() is None
    np.testing.assert_allclose(loop.release - loop.due(),
                               [0.0, 0.1, 0.05, 0.0, 0.4])


def test_open_loop_caps_the_batch_and_gives_up_after_the_drain():
    clk = FakeClock()
    loop = win.OpenLoop(np.zeros(5), max_batch=2, drain_s=1.0, clock=clk,
                        sleep=clk.sleep)
    loop.start(0.0)
    assert loop.next_batch() == (0, 2)
    clk.t = 2.0                              # past the drain
    assert loop.next_batch() is None
    assert loop.i == 2                       # three tasks never released


def test_live_layer_rows_read_the_traced_micro_batches_only():
    from harness import layers
    from harness.sut import Chunk

    # two warm-up chunks, then micro-batches of 2, 3 and 50 tasks; the trace
    # spans the first two, and the third came after a stall
    chunks = [Chunk(0.0, 0.0, n, {}) for n in (8, 8, 2, 3, 50)]
    late = np.array([1.0, 2.0, 3.0, 4.0, 5.0] + [900.0] * 50)
    ctx = {"chunks": chunks, "window_chunks": [2, 3, 4], "warm": 2,
           "traced": [2, 3],
           "info": {"gen_late_ms": late,
                    "chunk_of": [(0, 2), (2, 5), (5, 55)]}}
    assert layers.batch_rows_mean(ctx) == 2.5
    assert layers.gen_late_p95(ctx) == pytest.approx(
        np.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 95))
    del ctx["traced"]
    assert layers.batch_rows_mean(ctx) == 55 / 3
    assert layers.gen_late_p95(ctx) == 900.0
