"""Share of the traced micro-batches whose pull the stream loop held until the batch before went to the twin (the program's late_pulls counter)."""

from harness import spans


def read(ctx):
    return spans.per_batch(ctx, "late_pulls")
