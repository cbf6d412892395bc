"""Window arithmetic of the two traffic kinds, kept apart from the program
so that it can be tested on recorded times.

Replay (closed loop): chunks are fed back to back. The window opens when the
last warm-up chunk's outcomes are complete and closes at the end of the
first chunk that completes at or after ``seconds``: all the work over all the
time, and a stall cannot hide behind the window's edge.

Open loop: every task has a due time on the wall clock. Its decision
latency is the time its chunk's decisions reached the host minus its due
time; a task that was never decided counts at the drain's end.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np


def replay_close(t_out: list[float], warm: int, seconds: float):
    """Index of the chunk that closes the window (the first one after the
    warm-up whose end is at or after ``seconds`` past the window's start),
    or None while the window is open. ``t_out[k]`` is when chunk ``k``'s
    outcomes were complete."""
    if len(t_out) < warm:
        return None
    t0 = t_out[warm - 1]
    for k in range(warm, len(t_out)):
        if t_out[k] >= t0 + seconds:
            return k
    return None


def replay_rate(t_out: list[float], n: list[int], warm: int,
                seconds: float) -> tuple[float, int, float]:
    """``(tasks/s, tasks, window seconds)`` of a closed replay window."""
    k = replay_close(t_out, warm, seconds)
    if k is None:
        raise ValueError("the replay window never closed")
    tasks = int(sum(n[warm:k + 1]))
    span = t_out[k] - t_out[warm - 1]
    return tasks / span, tasks, span


def decision_latency_ms(due: np.ndarray, decided: np.ndarray,
                        drain_end: float) -> np.ndarray:
    """Per task (decided - due) in ms, on one clock in seconds; an undecided
    task (NaN) counts at ``drain_end``."""
    d = np.where(np.isnan(decided), drain_end, decided)
    return (d - due) * 1e3


def percentile(x: np.ndarray, q: float) -> float:
    """The ``q``-th percentile over all values (linear between ranks)."""
    if len(x) == 0:
        raise ValueError("no values")
    return float(np.percentile(np.asarray(x, np.float64), q))


class OpenLoop:
    """Releases tasks on the wall clock: each call hands back every task
    due by now (waiting for the next one if none is), at most ``max_batch``.
    ``offsets`` are the tasks' due times in seconds after ``start``.

    Release waits for ``start`` (the window's opening, which may come from
    another thread), and stops once every task is out, or ``drain_s`` after
    the last task was due; the tasks left then are never decided."""

    def __init__(self, offsets: np.ndarray, max_batch: int, drain_s: float,
                 clock=time.perf_counter, sleep=time.sleep):
        self.off = np.asarray(offsets, np.float64)
        self.max_batch = int(max_batch)
        self.drain_s = float(drain_s)
        self.clock, self.sleep = clock, sleep
        self.t0 = math.nan
        self.i = 0
        self.release = np.full(self.off.shape[0], np.nan)
        self._started = threading.Event()

    def start(self, t0: float) -> None:
        self.t0 = t0
        self._started.set()

    def due(self) -> np.ndarray:
        return self.t0 + self.off

    def next_batch(self):
        """``(lo, hi)`` of the next released tasks, or None when done."""
        n = self.off.shape[0]
        if self.i >= n:
            return None
        if not self._started.wait(timeout=3600.0):
            raise RuntimeError("the open-loop window never opened")
        last_due = self.t0 + (self.off[-1] if n else 0.0)
        now = self.clock()
        wait = self.t0 + self.off[self.i] - now
        if wait > 0:
            self.sleep(wait)
            now = self.clock()
        if now > last_due + self.drain_s:
            return None
        hi = int(np.searchsorted(self.off, now - self.t0, side="right"))
        hi = max(self.i + 1, min(hi, self.i + self.max_batch))
        lo, self.i = self.i, hi
        self.release[lo:hi] = now
        return lo, hi
