"""The ``drive`` of the bursty replay mix: the ``replay`` kind's, with each
stream's MMPP phase clock started where the stream starts.

``harness.arrivals.Stream`` draws its first phase end from time 0 whatever
``start_ms`` it is given. A window stream that continues a fixed-seed
warm-up (``warm_seed``) would then walk its phases from 0 again and put its
arrivals back before the warm-up's: a stream out of arrival order, which
the program serves on its host path. Here the first phase end moves by the
stream's start; every draw stays the same, and so does a stream from 0.

This file goes when ``Stream`` itself starts the clock at ``start_ms``
(``self._phase_end += self.t`` in its ``__init__``)."""

from harness import arrivals, driver


class _Stream(arrivals.Stream):
    def __init__(self, spec, process, seed, start_ms=0.0):
        super().__init__(spec, process, seed, start_ms)
        self._phase_end += self.t


def drive(*args):
    was = arrivals.Stream
    arrivals.Stream = _Stream
    try:
        return driver.KINDS["replay"](*args)
    finally:
        arrivals.Stream = was
