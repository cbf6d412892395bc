"""Mean time of the twin's execute_many per traced micro-batch but the last (the program's execute span)."""

from harness import spans


def read(ctx):
    return spans.twin_ms_per_batch(ctx)
