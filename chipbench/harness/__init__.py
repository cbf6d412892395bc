"""The benchmark's own code: spec loading, the window drivers, the trace
reduction, the peaks table, the deployment's fitted models, the plain
reference and the comparison that decides ``correct``. Nothing here is
imported by the program; the program is reached only through
``harness.sut``."""
