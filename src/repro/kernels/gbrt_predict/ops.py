"""Public wrapper: predict a fitted ``repro.core.gbrt.GBRT`` with the kernel."""

from __future__ import annotations

import threading
import weakref

import jax.numpy as jnp
import numpy as np

from repro.kernels import dfloat, interpret_mode
from repro.kernels.gbrt_predict.kernel import (
    CFG_BASE,
    CFG_LR,
    CFG_MEM,
    CFG_WIDTH,
    gbrt_predict_blocked,
)

_BIG = 3.0e38  # finite stand-in for +inf pass-through thresholds

# Device-operand caches, keyed on model identity with a weakref guard — the
# ``_CONST1_TABLES`` idiom (see ``repro.core.predictor``): an online refit
# swaps in a fresh model object, so the fresh id misses the cache and the
# stale entry is evicted on id recycle or the size-capped dead-ref sweep.
# Hosting the ensemble arrays once per model (not once per call/chunk) is
# what keeps the streaming serve path free of per-chunk host→device prep.
_OPERANDS: dict[int, tuple] = {}
_MULTI_OPERANDS: dict[tuple, tuple] = {}
_OPERAND_LOCK = threading.Lock()


def _cached(cache: dict, key, models, build):
    with _OPERAND_LOCK:
        hit = cache.get(key)
        if hit is not None:
            refs, val = hit
            if all(r() is m for r, m in zip(refs, models)):
                return val
            cache.pop(key, None)  # id recycled by a swap: stale
    val = build()
    try:
        refs = tuple(weakref.ref(m) for m in models)
    except TypeError:
        return val  # non-weakrefable model: serve uncached
    with _OPERAND_LOCK:
        if len(cache) > 128:  # drop entries whose model is gone
            for k in [k for k, (rs, _) in cache.items()
                      if any(r() is None for r in rs)]:
                cache.pop(k, None)
        cache[key] = (refs, val)
    return val


def _tree_tables(model, n_trees: int, depth: int) -> tuple:
    """One model's ensemble padded to ``(n_trees, depth)`` and flattened:
    ``(features (T*I,) i32, thr_hi, thr_lo (T*I,) f32, leaf_hi, leaf_lo
    (T*L,) f32)``, thresholds and leaves as two-float pairs.

    Padding is exact: extra trees are all pass-through (+big thresholds)
    with zero leaves — each adds exactly ``(0, 0)``; a depth-``d`` tree
    padded to ``depth`` walks on through pass-through levels (``x > +big``
    is never true) to the leftmost descendant, so leaf ``j`` moves to slot
    ``j << (depth - d)``. +inf thresholds are clipped to a finite big value
    before the split, which keeps every comparison's outcome.
    """
    d = int(model.config.max_depth)
    f = np.asarray(model.features, np.int32)
    t, i = f.shape
    n_int, n_leaf = 2 ** depth - 1, 2 ** depth
    F = np.zeros((n_trees, n_int), np.int32)
    TH = np.full((n_trees, n_int), _BIG)
    LV = np.zeros((n_trees, n_leaf))
    F[:t, :i] = f
    TH[:t, :i] = np.clip(np.asarray(model.thresholds, np.float64),
                         -_BIG, _BIG)
    LV[:t, ::1 << (depth - d)] = np.asarray(model.leaves, np.float64)
    thh, thl = dfloat.split(TH.ravel())
    lvh, lvl = dfloat.split(LV.ravel())
    return F.ravel(), thh, thl, lvh, lvl


def _cfg_row(model, memory_mb: float) -> np.ndarray:
    row = np.zeros(CFG_WIDTH, np.float32)
    row[CFG_MEM] = np.float32(memory_mb)
    row[CFG_LR:CFG_LR + 2] = dfloat.split(float(model.config.learning_rate))
    row[CFG_BASE:CFG_BASE + 2] = dfloat.split(float(model.base))
    return row


def kernel_operands(model) -> tuple:
    """Device-ready operands for ``gbrt_predict_blocked``: the flattened
    ensemble tables (see ``_tree_tables``) and the ``cfg`` row, each with a
    leading axis of 1, plus ``depth``. Hosted once per model identity
    (weakref-guarded — refit-by-swap invalidates automatically).
    """
    def build():
        depth = int(model.config.max_depth)
        tabs = _tree_tables(model, int(np.asarray(model.features).shape[0]),
                            depth)
        return tuple(jnp.asarray(a[None, :]) for a in tabs) \
            + (jnp.asarray(_cfg_row(model, 0.0)[None, :]), depth)

    return _cached(_OPERANDS, id(model), (model,), build)


def multi_kernel_operands(models, memory_mb) -> tuple:
    """Stacked, padded operands for the blocked ``gbrt_predict_multi`` launch.

    Pads every config's ensemble to the common ``(T, depth)`` of the
    largest one (``_tree_tables``), so a single (configs, row-blocks) grid
    covers them all while staying BIT-IDENTICAL per config to the
    per-config launch. ``memory_mb[c]`` is config ``c``'s memory feature.

    Returns ``(features, thr_hi, thr_lo, leaf_hi, leaf_lo, cfg, depth)``,
    all but ``depth`` as jnp arrays with a leading config axis. Cached per
    (model-identity tuple, memory features) — weakref-guarded, refit-by-swap
    safe.
    """
    models = tuple(models)
    mems = tuple(float(m) for m in memory_mb)

    def build():
        depth = max(int(m.config.max_depth) for m in models)
        n_trees = max(int(np.asarray(m.features).shape[0]) for m in models)
        tabs = [_tree_tables(m, n_trees, depth) for m in models]
        stacks = tuple(jnp.asarray(np.stack(col)) for col in zip(*tabs))
        cfg = np.stack([_cfg_row(m, mem) for m, mem in zip(models, mems)])
        return stacks + (jnp.asarray(cfg), depth)

    key = (tuple(id(m) for m in models), mems)
    return _cached(_MULTI_OPERANDS, key, models, build)


def gbrt_predict(model, x, *, interpret: bool | None = None) -> np.ndarray:
    """model: repro.core.gbrt.GBRT; x: (N, F). Returns float64 (N,)."""
    if interpret is None:
        interpret = interpret_mode()
    x = np.asarray(x, np.float64)
    if x.ndim == 1:
        x = x[:, None]
    if x.shape[0] == 0:
        return np.zeros(0)
    hi, lo = dfloat.split(x.T)                              # (F, N) each
    *ops, depth = kernel_operands(model)
    out_hi, out_lo = gbrt_predict_blocked(
        jnp.asarray(np.stack([hi, lo], axis=1)), *ops, depth=depth,
        interpret=interpret)
    return dfloat.join(out_hi, out_lo)
