"""Device time of the place program per 1000 tasks, over the traced micro-batches."""

from harness import layers


def read(ctx):
    return layers.program_ms_per_ktask(ctx, "place")
