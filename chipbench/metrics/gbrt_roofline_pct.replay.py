"""The GBRT kernel's share of its roofline: least time at the chip's peaks, from the model's shapes, over the kernel's device time."""

from harness import layers


def read(ctx):
    return layers.gbrt_roofline(ctx)
