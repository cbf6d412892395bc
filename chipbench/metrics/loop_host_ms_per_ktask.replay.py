"""Host time of the stream loop outside the device wait and the twin (the program's fetch_wait, pre_place, predict, d2h and tail spans) per 1000 tasks, over the window's chunks."""

from harness import spans


def read(ctx):
    spans.note_cycles(ctx, ctx["window_chunks"])
    return spans.ms_per_ktask(ctx, ctx["window_chunks"], spans.LOOP_HOST)
