"""Mean over the traced micro-batches' tasks of the time from the loop taking their batch to its decisions on the host (the program's pre_place, predict, place and d2h spans)."""

from harness import spans
from harness.layers import _spanned


def read(ctx):
    spans.note_cycles(ctx, _spanned(ctx))
    return spans.task_weighted_ms(ctx, _spanned(ctx), spans.DECIDE)
