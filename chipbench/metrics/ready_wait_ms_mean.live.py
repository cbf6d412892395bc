"""Mean over the traced micro-batches' tasks of the time their staged batch waited for the stream loop to take it (the program's ready_wait span)."""

from harness import spans
from harness.layers import _spanned


def read(ctx):
    spans.note_latency_parts(ctx)
    return spans.task_weighted_ms(ctx, _spanned(ctx), ("ready_wait",))
