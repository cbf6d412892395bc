"""Device time of the predict program (the GBRT kernel included) per 1000 tasks, over the traced chunks."""

from harness import layers


def read(ctx):
    return layers.program_ms_per_ktask(ctx, "predict")
