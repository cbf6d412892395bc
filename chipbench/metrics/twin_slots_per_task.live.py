"""Container slots the twin's pool walk visits per task, over the traced micro-batches but the last (the program's twin_slots counter)."""

from harness import spans


def read(ctx):
    return spans.twin_slots_per_task(ctx)
