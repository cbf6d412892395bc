"""Task streams made from the seed: arrival times and input sizes.

One general generator reads a traffic file's parameters. Arrival processes:

- ``poisson``: exponential gaps at ``rate_per_s`` (copied from
  ``repro.core.workload.PoissonWorkload``);
- ``mmpp``: quiet/burst phases at ``rate_per_s`` and ``rate_per_s x
  burst_multiplier``, exponential phase lengths of mean ``mean_quiet_s`` /
  ``mean_burst_s`` (copied from ``repro.core.workload.BurstyWorkload``'s
  phase walk).

Sizes follow the application's input distribution
(``harness.models.sample_inputs``). Everything is drawn from one
``numpy.random.default_rng(seed)``, block by block: the same seed and the
same block sizes give the same stream.
"""

from __future__ import annotations

import numpy as np

from harness.models import sample_inputs


class Stream:
    """An endless task stream of one application, generated block by block.

    ``block(m)`` returns the next ``m`` tasks as ``(arrival times in ms
    after ``start_ms``'s origin, sizes, payload bytes)``; ``scale``
    multiplies the arrival rate for that block."""

    def __init__(self, spec: dict, process: dict, seed: int,
                 start_ms: float = 0.0):
        self.spec = spec
        self.process = process
        self.rng = np.random.default_rng(seed)
        self.t = float(start_ms)
        kind = process["kind"]
        if kind not in ("poisson", "mmpp"):
            raise ValueError(f"unknown arrival process {kind!r}")
        self._burst = False
        self._phase_end = (self.rng.exponential(process["mean_quiet_s"] * 1e3)
                           if kind == "mmpp" else np.inf)

    def _gaps(self, m: int, scale: float) -> np.ndarray:
        p = self.process
        if p["kind"] == "poisson":
            return self.rng.exponential(1000.0 / (p["rate_per_s"] * scale),
                                        size=m)
        out = np.empty(m)
        t = prev = self.t
        j = 0
        while j < m:
            rate = scale * p["rate_per_s"] * (
                p["burst_multiplier"] if self._burst else 1.0)
            gap = self.rng.exponential(1000.0 / rate)
            if t + gap >= self._phase_end:
                # phase switch: exponential gaps are memoryless, so the gap
                # is drawn again at the new phase's rate
                t = self._phase_end
                self._burst = not self._burst
                mean = p["mean_burst_s"] if self._burst else p["mean_quiet_s"]
                self._phase_end = t + self.rng.exponential(mean * 1e3)
                continue
            t += gap
            out[j] = t - prev
            prev = t
            j += 1
        return out

    def block(self, m: int, scale: float = 1.0):
        gaps = self._gaps(m, scale)
        arr = self.t + np.cumsum(gaps)
        self.t = float(arr[-1]) if m else self.t
        size, nbytes = sample_inputs(self.spec, self.rng, m)
        return arr, size, nbytes


def warm_and_window(spec: dict, process: dict, seed: int, warm_rows,
                    warm_scales, warm_seed):
    """The warm-up blocks (``warm_rows[k]`` tasks at ``warm_scales[k]``
    times the rate) and the stream the window continues with. The warm-up
    comes from ``warm_seed`` where the traffic fixes one, so that every run
    enters its window from the same history; the window from ``seed``."""
    first = Stream(spec, process, seed if warm_seed is None else warm_seed)
    warm = [first.block(int(m), float(x))
            for m, x in zip(warm_rows, warm_scales)]
    if warm_seed is None:
        return warm, first
    return warm, Stream(spec, process, seed, start_ms=first.t)
