"""Host time in the twin's execute_many per 1000 tasks over the window's chunks (the benchmark's span around the call)."""

from harness import layers


def read(ctx):
    return layers.twin_ms_per_ktask(ctx)
