"""Rows whose least cost the place program's cost ranks split where its two-float arithmetic saw a tie, per 1000 tasks, over the window's chunks (the program's cost_rank_splits counter)."""

from harness.spans import _deltas


def read(ctx):
    ks = ctx["window_chunks"]
    d = _deltas(ctx, ks, ("cost_rank_splits",))
    n = sum(ctx["chunks"][k].n for k in ks)
    return float(d.sum() / (n / 1e3)) if d is not None and n else None
