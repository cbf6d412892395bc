"""GBRT ensemble inference Pallas TPU kernel — the Predictor's hot loop.

The paper's Decision Engine calls the GBRT compute-time model once per
(input × configuration); a serving fleet with thousands of placement decisions
per second amortizes them by *batching* prediction rows, which is exactly what
this kernel serves.

TPU adaptation of tree traversal (a scattered-memory GPU/CPU workload): trees
are complete (heap layout, pass-through nodes use a huge threshold), so the
traversal is a fixed ``depth``-step index walk with no divergence.

- **Rows on lanes.** Rows arrive as ``(rows/128, 128)`` tiles, so every
  vector op works on full ``(8, 128)`` vregs and the output is lane-dense.
- **Trees in SMEM.** Per-tree feature ids, thresholds and leaves are scalars
  read at ``(config, tree, node)``; a level of the walk selects its node's
  scalars with ``depth``-bounded ``where`` chains (``2**level`` candidates),
  so the kernel never issues a data-dependent vector load.
- **Float64 decisions in f32.** Inputs, thresholds, leaves, the learning rate
  and the accumulator are two-float pairs (``repro.kernels.dfloat``): splits
  compare in the oracle's float64 order, and ``acc + lr * leaf`` carries
  ~48 bits, so the predicted compute time agrees with the numpy ensemble to
  ~1e-14 relative instead of f32's ~1e-7.

Grid is ``(configs, row blocks)`` for the multi-config launch and
``(row blocks,)`` for one model; trees accumulate through a ``fori_loop``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dfloat

LANES = 128
BLOCK_ROWS = 8     # sublane rows per grid step: one (8, 128) vreg per value
# Per-config scalar layout of the ``cfg`` operand: memory feature, learning
# rate (hi, lo), ensemble base (hi, lo).
CFG_MEM, CFG_LR, CFG_BASE, CFG_WIDTH = 0, 1, 3, 5


def _ensemble(feature, row, feat_ref, thh_ref, thl_ref, lvh_ref, lvl_ref,
              cfg_ref, *, depth: int, n_trees: int, shape):
    """Sum one (padded) ensemble over a ``shape`` tile of rows.

    ``feature(f)`` maps an int32 tile of feature ids to the two-float
    feature values of the rows. Refs are whole 2-D SMEM arrays; ``row``
    selects this config's flattened ``(trees, nodes)`` tables.
    """
    n_int, n_leaf = 2 ** depth - 1, 2 ** depth
    lr = (cfg_ref[row, CFG_LR], cfg_ref[row, CFG_LR + 1])

    def pick(refs, base, idx, lo, hi):
        """Per-row scalar of ``refs`` at node ``idx`` in ``[lo, hi)``."""
        outs = [jnp.full(shape, r[row, base + lo], r.dtype) for r in refs]
        for i in range(lo + 1, hi):
            hit = idx == i
            outs = [jnp.where(hit, r[row, base + i], o)
                    for r, o in zip(refs, outs)]
        return outs

    def tree_step(t, acc):
        base = t * n_int
        node = jnp.zeros(shape, jnp.int32)
        for level in range(depth):                 # static unroll
            f, th, tl = pick((feat_ref, thh_ref, thl_ref), base, node,
                             2 ** level - 1, 2 ** (level + 1) - 1)
            right = dfloat.gt(feature(f), (th, tl))
            node = 2 * node + 1 + right.astype(jnp.int32)
        ch, cl = pick((lvh_ref, lvl_ref), t * n_leaf, node - n_int, 0, n_leaf)
        return dfloat.add(acc, dfloat.mul((ch, cl), lr))

    acc = (jnp.full(shape, cfg_ref[row, CFG_BASE], jnp.float32),
           jnp.full(shape, cfg_ref[row, CFG_BASE + 1], jnp.float32))
    return jax.lax.fori_loop(0, n_trees, tree_step, acc)


def _multi_kernel(x_ref, feat_ref, thh_ref, thl_ref, lvh_ref, lvl_ref,
                  cfg_ref, o_ref, *, depth: int, n_trees: int):
    """One (config, row-block) cell: feature 0 is the shared size column,
    feature 1 the config's memory (a per-config SMEM scalar)."""
    c = pl.program_id(0)
    sh, sl = x_ref[0], x_ref[1]                    # (bs, 128) each
    mem = cfg_ref[c, CFG_MEM]

    def feature(f):
        size = f == 0
        return jnp.where(size, sh, mem), jnp.where(size, sl, 0.0)

    hi, lo = _ensemble(feature, c, feat_ref, thh_ref, thl_ref, lvh_ref,
                       lvl_ref, cfg_ref, depth=depth, n_trees=n_trees,
                       shape=sh.shape)
    o_ref[0, 0] = hi
    o_ref[1, 0] = lo


def _single_kernel(x_ref, feat_ref, thh_ref, thl_ref, lvh_ref, lvl_ref,
                   cfg_ref, o_ref, *, depth: int, n_trees: int):
    """One row block of a single model over ``F`` explicit features."""
    n_feat = x_ref.shape[0]
    cols = [(x_ref[j, 0], x_ref[j, 1]) for j in range(n_feat)]

    def feature(f):
        hi, lo = cols[n_feat - 1]
        for j in range(n_feat - 2, -1, -1):
            hit = f == j
            hi = jnp.where(hit, cols[j][0], hi)
            lo = jnp.where(hit, cols[j][1], lo)
        return hi, lo

    hi, lo = _ensemble(feature, 0, feat_ref, thh_ref, thl_ref, lvh_ref,
                       lvl_ref, cfg_ref, depth=depth, n_trees=n_trees,
                       shape=cols[0][0].shape)
    o_ref[0] = hi
    o_ref[1] = lo


def _row_tiles(n: int) -> tuple[int, int]:
    """(padded sublane rows, block sublane rows) for ``n`` rows on lanes."""
    s = -(-n // LANES)
    bs = min(s, BLOCK_ROWS)
    return -(-s // bs) * bs, bs


def _to_tiles(x, s_pad: int):
    """(..., N) -> (..., s_pad, 128), edge-padded."""
    n = x.shape[-1]
    pad = s_pad * LANES - n
    if pad:
        x = jnp.concatenate(
            [x, jnp.broadcast_to(x[..., -1:], x.shape[:-1] + (pad,))], axis=-1)
    return x.reshape(x.shape[:-1] + (s_pad, LANES))


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def gbrt_predict_multi(x, features, thr_hi, thr_lo, leaf_hi, leaf_lo, cfg, *,
                       depth: int, interpret: bool = False):
    """ALL cloud configs in one blocked launch — grid (configs, row blocks).

    ``x``: (2, N) f32 — the shared size column as a two-float (hi, lo) pair.
    ``features`` (C, T*I) i32, ``thr_hi``/``thr_lo`` (C, T*I) f32,
    ``leaf_hi``/``leaf_lo`` (C, T*L) f32: the padded, flattened per-config
    ensembles (``ops.multi_kernel_operands``); ``cfg`` (C, CFG_WIDTH) f32:
    memory feature, learning rate and base per config. Returns ``(hi, lo)``,
    each (C, N) f32 — row ``c`` equals a per-config ``gbrt_predict_blocked``
    launch bit for bit.
    """
    n = x.shape[1]
    C = features.shape[0]
    T = features.shape[1] // (2 ** depth - 1)
    s_pad, bs = _row_tiles(n)
    xt = _to_tiles(x, s_pad)                               # (2, S, 128)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_multi_kernel, depth=depth, n_trees=T)
    out = pl.pallas_call(
        kernel,
        grid=(C, s_pad // bs),
        in_specs=[pl.BlockSpec((2, bs, LANES),
                               lambda c, i: (0, i, 0))] + [smem] * 6,
        out_specs=pl.BlockSpec((2, 1, bs, LANES), lambda c, i: (0, c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, C, s_pad, LANES), jnp.float32),
        interpret=interpret,
    )(xt, features, thr_hi, thr_lo, leaf_hi, leaf_lo, cfg)
    out = out.reshape(2, C, s_pad * LANES)[:, :, :n]
    return out[0], out[1]


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def gbrt_predict_blocked(x, features, thr_hi, thr_lo, leaf_hi, leaf_lo, cfg,
                         *, depth: int, interpret: bool = False):
    """One model. ``x``: (F, 2, N) f32 — F features as two-float pairs;
    ensemble operands as in ``gbrt_predict_multi`` with C == 1 (``cfg``'s
    memory slot unused). Returns ``(hi, lo)``, each (N,) f32."""
    n_feat, _, n = x.shape
    T = features.shape[1] // (2 ** depth - 1)
    s_pad, bs = _row_tiles(n)
    xt = _to_tiles(x, s_pad)                               # (F, 2, S, 128)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_single_kernel, depth=depth, n_trees=T)
    out = pl.pallas_call(
        kernel,
        grid=(s_pad // bs,),
        in_specs=[pl.BlockSpec((n_feat, 2, bs, LANES),
                               lambda i: (0, 0, i, 0))] + [smem] * 6,
        out_specs=pl.BlockSpec((2, bs, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, s_pad, LANES), jnp.float32),
        interpret=interpret,
    )(xt, features, thr_hi, thr_lo, leaf_hi, leaf_lo, cfg)
    out = out.reshape(2, s_pad * LANES)[:, :n]
    return out[0], out[1]
