"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs. pure-jnp oracle.

Every kernel in repro.kernels is validated against its ref.py across a sweep
of shapes, GQA group sizes, masks, chunk sizes and dtypes, per the assignment.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.linear_scan.ops import linear_scan
from repro.kernels.linear_scan.ref import linear_scan_ref
from repro.kernels.gbrt_predict.ops import gbrt_predict
from repro.kernels.gbrt_predict.ref import gbrt_predict_ref
from repro.core.gbrt import GBRT, GBRTConfig

F32, BF16 = jnp.float32, jnp.bfloat16


def _tol(dt):
    return 3e-2 if dt == BF16 else 5e-5


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Sq,H,Hkv,D", [
    (1, 64, 2, 1, 32),     # MQA
    (2, 128, 4, 2, 64),    # GQA
    (1, 96, 4, 4, 16),     # MHA, padded seq (96 -> 128 with bq=64? 96%32)
    (1, 256, 8, 1, 128),   # long-ish MQA
])
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_flash_attention_sweep(B, Sq, H, Hkv, D, window, dtype, rng):
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    err = np.max(np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)))
    assert err < _tol(dtype), err


def test_flash_attention_bidirectional(rng):
    """Encoder (non-causal) path."""
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), F32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 32)), F32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 32)), F32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=False)
    assert np.max(np.abs(np.asarray(out - ref))) < 5e-5


# ----------------------------------------------------------- decode attention
@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 128, 4, 1, 32),
    (3, 200, 8, 2, 64),    # padded cache (200 % 64 != 0)
    (1, 64, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_decode_attention_sweep(B, S, H, Hkv, D, dtype, rng):
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=64)
    ref = decode_attention_ref(q, k, v, lengths)
    err = np.max(np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)))
    assert err < _tol(dtype), err


def test_decode_attention_length_one(rng):
    """Degenerate cache: only slot 0 valid → output == v[:, 0]."""
    B, S, H, D = 2, 32, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), F32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), F32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), F32)
    out = decode_attention(q, k, v, jnp.ones((B,), jnp.int32), block_k=16)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ ssd scan
@pytest.mark.parametrize("b,S,nh,hd,ds,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 4, 16, 16, 16),
    (1, 100, 2, 8, 8, 32),   # padded tail chunk
])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_ssd_scan_sweep(b, S, nh, hd, ds, chunk, dtype, rng):
    x = jnp.asarray(rng.normal(size=(b, S, nh, hd)), dtype)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, S, nh))) * 0.5, F32)
    A = jnp.asarray(-np.abs(rng.normal(size=(nh,))) - 0.1, F32)
    B_ = jnp.asarray(rng.normal(size=(b, S, ds)), dtype)
    C = jnp.asarray(rng.normal(size=(b, S, ds)), dtype)
    y, st = ssd(x, dt, A, B_, C, chunk=chunk)
    yr, sr = ssd_ref(x, dt, A, B_, C)
    ye = np.max(np.abs(np.asarray(y, np.float32) - np.asarray(yr, np.float32)))
    se = np.max(np.abs(np.asarray(st) - np.asarray(sr)))
    assert ye < (1e-1 if dtype == BF16 else 1e-3), ye
    assert se < (5e-2 if dtype == BF16 else 1e-3), se


def test_ssd_state_carried_across_chunks(rng):
    """Final state must equal the literal recurrence even with many chunks."""
    b, S, nh, hd, ds = 1, 64, 2, 4, 4
    x = jnp.asarray(rng.normal(size=(b, S, nh, hd)), F32)
    dt = jnp.asarray(np.full((b, S, nh), 0.3), F32)
    A = jnp.asarray([-0.5, -1.0], F32)
    B_ = jnp.asarray(rng.normal(size=(b, S, ds)), F32)
    C = jnp.asarray(rng.normal(size=(b, S, ds)), F32)
    _, st8 = ssd(x, dt, A, B_, C, chunk=8)
    _, st64 = ssd(x, dt, A, B_, C, chunk=64)
    np.testing.assert_allclose(np.asarray(st8), np.asarray(st64), atol=1e-4)


# --------------------------------------------------------------- linear scan
@pytest.mark.parametrize("B,S,D,chunk", [
    (1, 16, 8, 8), (2, 64, 32, 16), (1, 100, 16, 32), (3, 7, 4, 8),
])
def test_linear_scan_sweep(B, S, D, chunk, rng):
    x = jnp.asarray(rng.normal(size=(B, S, D)), F32)
    a = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, S, D)), F32)
    y, st = linear_scan(x, a, chunk=chunk)
    yr, sr = linear_scan_ref(x, a)
    assert np.max(np.abs(np.asarray(y - yr))) < 1e-5
    assert np.max(np.abs(np.asarray(st - sr))) < 1e-5


def test_linear_scan_identity_decay(rng):
    """a == 1 everywhere → h is a running sum (prefix-sum check)."""
    x = jnp.asarray(rng.normal(size=(1, 32, 4)), F32)
    a = jnp.ones((1, 32, 4), F32)
    y, st = linear_scan(x, a, chunk=8)
    np.testing.assert_allclose(np.asarray(y), np.cumsum(np.asarray(x), axis=1),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- gbrt predict
@pytest.mark.parametrize("n_features,depth,n_trees", [(1, 2, 20), (2, 3, 50), (3, 4, 10)])
def test_gbrt_predict_sweep(n_features, depth, n_trees, rng):
    x = rng.normal(size=(400, n_features)) * 100.0
    y = x[:, 0] * 2.0 + np.sin(x[:, -1] / 30.0) * 10.0 + rng.normal(size=400)
    m = GBRT.fit(x, y, GBRTConfig(n_trees=n_trees, max_depth=depth))
    xq = rng.normal(size=(137, n_features)) * 100.0
    pk = gbrt_predict(m, xq)
    pr = gbrt_predict_ref(xq.astype(np.float32), m.features, m.thresholds,
                          m.leaves, depth=depth,
                          lr=m.config.learning_rate, base=m.base)
    np.testing.assert_allclose(pk, pr, rtol=1e-4, atol=1e-4)
    # and against the numpy production path
    np.testing.assert_allclose(pk, m.predict(xq), rtol=1e-4, atol=1e-4)


def test_gbrt_predict_multi_matches_per_config(rng):
    """The blocked multi-config launch (one grid over the padded operand
    stack) is BIT-identical per config to a per-config launch — including
    heterogeneous depths/tree counts and a repeated model (shared id) — and
    its two-float result agrees with the float64 tree walk."""
    from repro.kernels import dfloat
    from repro.kernels.gbrt_predict.kernel import (
        gbrt_predict_blocked,
        gbrt_predict_multi,
    )
    from repro.kernels.gbrt_predict.ops import (
        kernel_operands,
        multi_kernel_operands,
    )

    models = []
    for depth, trees in [(2, 20), (3, 50), (4, 10)]:
        x = rng.normal(size=(300, 2)) * 100.0
        y = x[:, 0] * 2.0 + np.sin(x[:, 1] / 30.0) * 10.0
        models.append(GBRT.fit(x, y, GBRTConfig(n_trees=trees,
                                                max_depth=depth)))
    models.append(models[0])  # same model under two configs
    mems = [1280.0, 1536.0, 1792.0, 2048.0]
    sizes = rng.normal(size=(256,)) * 100.0

    *ops, dmax = multi_kernel_operands(models, mems)
    hi, lo = dfloat.split(sizes)
    mh, ml = gbrt_predict_multi(jnp.asarray(np.stack([hi, lo])), *ops,
                                depth=dmax, interpret=True)
    assert mh.shape == (len(models), 256)
    for c, (m, mem) in enumerate(zip(models, mems)):
        *single_ops, depth = kernel_operands(m)
        mem_hi, mem_lo = dfloat.split(np.full(256, mem))
        x2 = np.stack([np.stack([hi, lo]), np.stack([mem_hi, mem_lo])])
        sh, sl = gbrt_predict_blocked(jnp.asarray(x2), *single_ops,
                                      depth=depth, interpret=True)
        assert np.array_equal(np.asarray(mh[c]), np.asarray(sh)), c
        assert np.array_equal(np.asarray(ml[c]), np.asarray(sl)), c
        ref = m.predict(np.stack([sizes, np.full(256, mem)], axis=1))
        np.testing.assert_allclose(dfloat.join(sh, sl), ref, rtol=1e-12,
                                   atol=1e-9)


def test_gbrt_operand_caches(rng):
    """Kernel operands are hosted once per model identity (weakref-guarded —
    a refit-by-swap misses and re-hosts), for both the per-config and the
    stacked multi-config form."""
    from repro.kernels.gbrt_predict.ops import (
        kernel_operands,
        multi_kernel_operands,
    )

    x = rng.normal(size=(200, 1)) * 100.0
    y = x[:, 0] * 1.5
    m1 = GBRT.fit(x, y, GBRTConfig(n_trees=8, max_depth=2))
    ops1 = kernel_operands(m1)
    assert kernel_operands(m1) is ops1
    multi1 = multi_kernel_operands((m1, m1), (1280.0, 1536.0))
    assert multi_kernel_operands((m1, m1), (1280.0, 1536.0)) is multi1
    m2 = GBRT.fit(x, y, GBRTConfig(n_trees=8, max_depth=2))  # "refit"
    assert kernel_operands(m2) is not ops1
    assert multi_kernel_operands((m1, m2), (1280.0, 1536.0)) is not multi1
