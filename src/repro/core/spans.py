"""Per-chunk spans of a device-backed ``serve_stream``.

One ``Spans`` recorder lives for one stream. The consumer thread's spans
tile the stream loop: each starts at the clock read that ends the one
before it, so between two twin calls every second lands in exactly one of

- ``fetch_wait`` — the loop blocked for its next chunk;
- ``pre_place`` — the hooks before placement (``_pre_place``,
  ``_snapshot_horizons``);
- ``predict`` — ``place_many`` up to the place program's call: task-array
  assembly, eager conversions, the predict dispatch (on a chunk the core
  refuses, the whole host placement);
- ``place`` — the place program, until its ``overflow`` / ``converged``
  flags are read, retries included: the host waiting on the device;
- ``d2h`` — the decision outputs read back and the state commit;
- ``execute`` — the backend's ``execute_many`` (the twin);
- ``tail`` — record assembly, the arena append, ``_post_execute``.

Two more are measured off the consumer thread and added when the loop takes
the chunk: ``stage`` (the transfer thread's ``stage_chunk``: task arrays,
padding, ``device_put``) and ``ready_wait`` (from the staged chunk being
ready to the loop taking it: the prefetch queue). After a full chunk the
next pull starts as the loop takes it, and ``ready_wait`` is what is left of
that chunk's cycle once the next one is staged. After a short chunk (a
caught-up source, which has nothing more to give until time passes) the
pull waits for the chunk's backend call, so ``ready_wait`` is only what is
left of the backend call and the ``tail`` once staging is done.

Every consumer span, and ``stage`` on the transfer thread, is also a
``jax.profiler.TraceAnnotation(<span>, chunk=<sequence number>)``, so a
profile shows them on the host plane, on the device trace's clock. Spans
of one chunk share its sequence number. ``ready_wait`` has no annotation:
it is a wait between two threads, and an event covering it would overlap
every span of the cycle it waits through.

Totals are flat, monotone seconds over the stream (``totals``); the runtime
publishes them in ``engine.jax_stats`` before each backend call and in
``stream_stats["spans"]`` at the stream's end.
"""

from __future__ import annotations

import time

CONSUMER = ("fetch_wait", "pre_place", "predict", "place", "d2h", "execute",
            "tail")
OFF_LOOP = ("stage", "ready_wait")


class Spans:
    """The span recorder of one stream (see the module docstring)."""

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self.annotate = TraceAnnotation
        self.totals = dict.fromkeys(CONSUMER + OFF_LOOP, 0.0)
        self.chunk = 0           # sequence number of the chunk in the loop
        self._open = None        # (span, start, annotation)

    def switch(self, name: str) -> None:
        """End the open span and start ``name`` at the same clock read; a
        no-op while ``name`` is the open span."""
        op = self._open
        if op is not None and op[0] == name:
            return
        t = time.perf_counter()
        if op is not None:
            self.totals[op[0]] += t - op[1]
            op[2].__exit__(None, None, None)
        ann = self.annotate(name, chunk=self.chunk)
        ann.__enter__()
        self._open = (name, t, ann)

    def stop(self) -> None:
        """End the open span, if any."""
        op = self._open
        if op is not None:
            self.totals[op[0]] += time.perf_counter() - op[1]
            op[2].__exit__(None, None, None)
            self._open = None
