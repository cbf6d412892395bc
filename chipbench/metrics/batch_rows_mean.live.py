"""Mean tasks per micro-batch over the traced micro-batches."""

from harness import layers


def read(ctx):
    return layers.batch_rows_mean(ctx)
