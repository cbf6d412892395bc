"""95th percentile of (release into the service - due time) over the traced micro-batches' tasks."""

from harness import layers


def read(ctx):
    return layers.gen_late_p95(ctx)
