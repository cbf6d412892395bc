"""Readings of the program's own per-chunk spans and counters.

A device-backed stream publishes flat, monotone stream totals in
``engine.jax_stats`` right before each twin call: span seconds
(``fetch_wait``, ``pre_place``, ``predict``, ``place``, ``d2h``,
``execute``, ``tail``, ``stage``, ``ready_wait``) and counts
(``d2h_reads``, ``twin_slots``, ``resident_regrows``). ``sut.TimedTwin``
copies that dict at the call, so chunk ``k``'s reading of a total is
``stats[k] - stats[k - 1]``: the loop cycle that ends when chunk ``k``'s
decisions reach the twin. It holds chunk ``k``'s own ``fetch_wait`` …
``d2h``, ``stage`` and ``ready_wait``, and chunk ``k - 1``'s ``execute``,
``tail`` and ``twin_slots``. Where a snapshot lacks a total (a program
that does not publish it) a reading is None.
"""

from __future__ import annotations

import numpy as np

from harness.layers import _spanned

CONSUMER = ("fetch_wait", "pre_place", "predict", "place", "d2h", "execute",
            "tail")
OFF_LOOP = ("stage", "ready_wait")
DECIDE = ("pre_place", "predict", "place", "d2h")
LOOP_HOST = ("fetch_wait", "pre_place", "predict", "d2h", "tail")


def _deltas(ctx, ks, keys):
    """Per chunk ``k`` of ``ks``, the growth of the sum of ``keys`` from
    chunk ``k - 1``'s snapshot to chunk ``k``'s; None where there is no
    chunk ``k`` or a snapshot lacks a total."""
    ch, out = ctx["chunks"], []
    for k in ks:
        if not 1 <= k < len(ch):
            return None
        a, b = ch[k - 1].stats, ch[k].stats
        if any(key not in a or key not in b for key in keys):
            return None
        out.append(sum(b[key] - a[key] for key in keys))
    return np.array(out, np.float64) if out else None


def ms_per_ktask(ctx, ks, keys):
    """Sum over chunks ``ks`` of their readings of ``keys``, in ms per
    1,000 of their tasks."""
    d = _deltas(ctx, ks, keys)
    n = sum(ctx["chunks"][k].n for k in ks)
    return float(d.sum() * 1e3 / (n / 1e3)) if d is not None and n else None


def task_weighted_ms(ctx, ks, keys):
    """Mean over the tasks of chunks ``ks`` of their chunk's reading of
    ``keys``, in ms."""
    d = _deltas(ctx, ks, keys)
    n = np.array([ctx["chunks"][k].n for k in ks], np.float64)
    return float((d * n).sum() * 1e3 / n.sum()) \
        if d is not None and n.sum() else None


def twin_batches(ctx) -> list[int]:
    """The spanned micro-batches but the last, whose twin call stopped the
    profiler (and whose ``execute`` no snapshot holds when untraced)."""
    return _spanned(ctx)[:-1]


def twin_ms_per_batch(ctx):
    """Mean twin call of the batches, in ms: batch ``k``'s ``execute`` is
    read at batch ``k + 1``."""
    ks = twin_batches(ctx)
    d = _deltas(ctx, [k + 1 for k in ks], ("execute",))
    return float(d.mean() * 1e3) if d is not None else None


def twin_slots_per_task(ctx):
    ks = twin_batches(ctx)
    d = _deltas(ctx, [k + 1 for k in ks], ("twin_slots",))
    n = sum(ctx["chunks"][k].n for k in ks)
    return float(d.sum() / n) if d is not None and n else None


def per_batch(ctx, key: str):
    """Mean reading of ``key`` over the spanned micro-batches."""
    d = _deltas(ctx, _spanned(ctx), (key,))
    return float(d.mean()) if d is not None else None


def note_cycles(ctx, ks) -> None:
    """Log each span's mean reading over the cycles ending at chunks
    ``ks``, and the tiling residual: each cycle (twin entry to twin entry)
    less the sum of its consumer spans."""
    ks = [k for k in ks if k >= 1]
    d = {key: _deltas(ctx, ks, (key,)) for key in CONSUMER + OFF_LOOP}
    if not ks or any(v is None for v in d.values()):
        return
    ch = ctx["chunks"]
    cycle = np.array([ch[k].t_in - ch[k - 1].t_in for k in ks])
    resid = cycle - sum(d[key] for key in CONSUMER)
    share = np.abs(resid) / cycle
    ctx.setdefault("notes", []).append(
        f"spans over {len(ks)} cycles, mean ms: " + ", ".join(
            f"{key} {v.mean() * 1e3:.6f}" for key, v in d.items())
        + f"; cycle {cycle.mean() * 1e3:.6f} ms; tiling residual median "
        f"{np.median(share) * 100:.6f}% (max {share.max() * 100:.6f}%, "
        f"median {np.median(resid) * 1e6:.3f} us)")


def note_latency_parts(ctx) -> None:
    """Log, over the spanned micro-batches' tasks, the decision latency
    (twin entry less due time) beside the sum of its parts: the release's
    lateness, the batch's ``stage``, ``ready_wait`` and decide spans."""
    info = ctx["info"]
    loop, chunk_of = info.get("loop"), info.get("chunk_of")
    ks = _spanned(ctx)
    parts = {key: _deltas(ctx, ks, keys) for key, keys in (
        ("stage", ("stage",)), ("ready_wait", ("ready_wait",)),
        ("decide", DECIDE))}
    if loop is None or chunk_of is None or not ks \
            or any(v is None for v in parts.values()):
        return
    due = loop.due()
    lat, late, per = [], [], {key: [] for key in parts}
    for i, k in enumerate(ks):
        lo, hi = chunk_of[k - ctx["warm"]]
        lat.append(ctx["chunks"][k].t_in - due[lo:hi])
        late.append(loop.release[lo:hi] - due[lo:hi])
        for key, v in parts.items():
            per[key].append(np.full(hi - lo, v[i]))
    lat, late = np.concatenate(lat), np.concatenate(late)
    per = {key: np.concatenate(v) for key, v in per.items()}
    total = late + sum(per.values())
    ctx.setdefault("notes", []).append(
        f"decision latency over {lat.size} tasks, ms: p50 "
        f"{np.median(lat) * 1e3:.6f}, mean {lat.mean() * 1e3:.6f}; parts, "
        f"mean: gen_late {late.mean() * 1e3:.6f}, " + ", ".join(
            f"{key} {v.mean() * 1e3:.6f}" for key, v in per.items())
        + f"; p50 of the sum {np.median(total) * 1e3:.6f}, mean residual "
        f"{(lat - total).mean() * 1e3:.6f}")
