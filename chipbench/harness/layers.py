"""Arithmetic shared by the per-layer metrics' readers
(``chipbench/metrics/<metric>.py``). A reader gets the run's context:

- ``chunks``: per chunk at the twin boundary (``sut.Chunk``): entry and exit
  times, tasks, the program's ``jax_stats`` of that chunk;
- ``window_chunks``: indices of the chunks in the measured window;
- ``warm``: index of the first chunk after the warm-up;
- ``traced``: indices of the chunks the trace spans (``--trace 1`` only);
- ``trace``: ``harness.trace.reduce``'s result (``--trace 1`` only);
- ``cfg``, ``tables``, ``device``, ``info``: the configuration, the fitted
  tables, the device and the traffic kind's own record.

A reader that finds nothing to read returns None."""

from __future__ import annotations

import numpy as np

from harness.peaks import peaks

BLOCK_ROWS = 32   # rows per block of ``passes_per_block``


def traced_tasks(ctx) -> int:
    return sum(ctx["chunks"][k].n for k in ctx.get("traced", []))


def program_ms_per_ktask(ctx, program: str):
    tr = ctx.get("trace")
    if not tr or program not in tr["programs"]:
        return None
    n = traced_tasks(ctx)
    return tr["programs"][program] * 1e3 / (n / 1e3) if n else None


def idle_pct(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def passes_per_block(ctx):
    passes = rows = 0
    for k in ctx["window_chunks"]:
        s = ctx["chunks"][k].stats
        if "passes" not in s or "rows" not in s:
            return None
        passes += s["passes"]
        rows += s["rows"]
    return passes / (rows / BLOCK_ROWS) if rows else None


def twin_ms_per_ktask(ctx):
    ch = [ctx["chunks"][k] for k in ctx["window_chunks"]]
    n = sum(c.n for c in ch)
    return sum(c.t_out - c.t_in for c in ch) * 1e3 / (n / 1e3) if n else None


def gbrt_work(rows: int, g: dict, n_configs: int) -> tuple[float, float]:
    """Operations and bytes of one GBRT launch over ``rows`` tasks, from the
    model's shapes: per row and config, each tree makes ``depth``
    comparisons and one leaf add; the launch reads each row's size feature
    (8 bytes), writes one 8-byte prediction per row and config, and reads
    the trees once (per internal node a 4-byte feature index and an 8-byte
    threshold, per leaf an 8-byte value)."""
    depth, trees = int(g["max_depth"]), int(g["n_trees"])
    ops = float(rows) * n_configs * trees * (depth + 1)
    table = trees * ((2 ** depth - 1) * (4 + 8) + 2 ** depth * 8)
    byts = float(rows) * 8 + float(rows) * n_configs * 8 + table
    return ops, byts


def gbrt_roofline(ctx):
    """Least time of the traced launches at the chip's peaks over their
    kernel time, in %; None without recorded kernel operations."""
    tr = ctx.get("trace")
    if not tr or not tr["kernel_calls"] or tr["kernel_s"] <= 0:
        return None
    pk = peaks(ctx["device"]["kind"])
    C = len(ctx["cfg"]["memory_configs_mb"])
    ops = byts = 0.0
    for i in tr["kernel_calls"]:
        o, b = gbrt_work(ctx["chunks"][ctx["warm"] + i].n,
                         ctx["tables"]["gbrt"], C)
        ops += o
        byts += b
    t_ops, t_bytes = ops / pk["flops"], byts / pk["hbm_bw"]
    ctx.setdefault("notes", []).append(
        f"gbrt roofline: {ops:.0f} ops, {byts:.0f} bytes over "
        f"{len(tr['kernel_calls'])} launches in {tr['kernel_s']:.9f} s; "
        f"{'compute' if t_ops >= t_bytes else 'memory'} bound")
    return 100.0 * max(t_ops, t_bytes) / tr["kernel_s"]


def _spanned(ctx) -> list[int]:
    """The chunks the trace spans, else the window's: the ``.live`` rows
    read the traced span, since stopping the profiler (it writes the trace
    out) stalls the open loop after it."""
    return ctx.get("traced") or ctx["window_chunks"]


def batch_rows_mean(ctx):
    ch = [ctx["chunks"][k] for k in _spanned(ctx)]
    return float(np.mean([c.n for c in ch])) if ch else None


def gen_late_p95(ctx):
    """p95 of (release - due) over the tasks of the spanned micro-batches."""
    late, chunk_of = ctx["info"].get("gen_late_ms"), ctx["info"].get(
        "chunk_of")
    if late is None or chunk_of is None:
        return None
    spans = [chunk_of[k - ctx["warm"]] for k in _spanned(ctx)]
    x = np.concatenate([late[lo:hi] for lo, hi in spans]) if spans else []
    return float(np.percentile(x, 95)) if len(x) else None
