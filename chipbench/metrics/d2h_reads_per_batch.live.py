"""Device arrays the placement core reads back to the host per traced micro-batch (the program's d2h_reads counter)."""

from harness import spans


def read(ctx):
    return spans.per_batch(ctx, "d2h_reads")
