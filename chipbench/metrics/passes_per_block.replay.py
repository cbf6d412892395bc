"""Fixed-point passes of the place program per 32-row block, summed over the window's chunks (the program's jax_stats counter)."""

from harness import layers


def read(ctx):
    return layers.passes_per_block(ctx)
