"""Two-float ("double-f32") arithmetic for f32-only devices.

A value is a pair ``(hi, lo)`` of f32 arrays with ``hi = fl32(hi + lo)``: an
unevaluated sum carrying ~48 significant bits. The TPU has no native float64,
and the placement oracle decides in float64 — latencies near a tie, costs near
the Alg. 1 budget, arrival times of 1e8+ ms. Two-float keeps those
comparisons resolved at about 2^-48 relative, where plain f32 (2^-24) flips
decisions. The algorithms are the classic error-free transformations
(Knuth's two-sum, Dekker's split product); they use only +, -, * and
compares, so they run unchanged inside Pallas kernels and XLA programs, and
stay exact if the compiler contracts a product into an FMA.

Comparisons are lexicographic on normalized pairs, which orders them exactly
like the reals they represent. ``add`` keeps infinities: the ``±inf``
sentinels of empty container slots stay ``(±inf, 0)``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_SPLIT = 4097.0  # 2**12 + 1: Dekker split of a 24-bit significand


# ------------------------------------------------------------------- host
def split(x) -> tuple[np.ndarray, np.ndarray]:
    """float64 -> (hi, lo) f32 pair; ``±inf`` maps to ``(±inf, 0)``."""
    x = np.asarray(x, np.float64)
    hi = x.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = np.where(np.isfinite(x), x - hi.astype(np.float64), 0.0)
    return hi, lo.astype(np.float32)


def join(hi, lo) -> np.ndarray:
    """(hi, lo) pair -> float64 (exact: the f64 sum of two f32 is exact
    whenever the pair is normalized)."""
    hi = np.asarray(hi, np.float64)
    lo = np.asarray(lo, np.float64)
    with np.errstate(invalid="ignore"):
        return np.where(np.isfinite(hi), hi + lo, hi)


# ----------------------------------------------------------------- device
def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):
    s = a + b
    return s, b - (s - a)


def add(x, y):
    """Accurate two-float sum (two two-sums, two renormalizations)."""
    (xh, xl), (yh, yl) = x, y
    s, e = _two_sum(xh, yh)
    t, f = _two_sum(xl, yl)
    s, e = _fast_two_sum(s, e + t)
    s, e = _fast_two_sum(s, e + f)
    raw = xh + yh
    ok = jnp.isfinite(raw)
    return jnp.where(ok, s, raw), jnp.where(ok, e, 0.0)


def sub(x, y):
    return add(x, (-y[0], -y[1]))


def _split(a):
    t = a * _SPLIT
    hi = t - (t - a)
    return hi, a - hi


def mul(x, y):
    """Two-float product of finite operands (Dekker's two-prod)."""
    (xh, xl), (yh, yl) = x, y
    p = xh * yh
    ah, al = _split(xh)
    bh, bl = _split(yh)
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return _fast_two_sum(p, e + (xh * yl + xl * yh))


def lt(x, y):
    return (x[0] < y[0]) | ((x[0] == y[0]) & (x[1] < y[1]))


def le(x, y):
    return (x[0] < y[0]) | ((x[0] == y[0]) & (x[1] <= y[1]))


def gt(x, y):
    return lt(y, x)


def eq(x, y):
    return (x[0] == y[0]) & (x[1] == y[1])


def where(c, x, y):
    return jnp.where(c, x[0], y[0]), jnp.where(c, x[1], y[1])


def maximum(x, y):
    return where(lt(x, y), y, x)


def round_half_even(x):
    """``np.round`` of the represented value, as an f32 integer (exact for
    magnitudes below 2**24)."""
    hi, lo = x
    r = jnp.round(hi)
    d = hi - r                      # exact: |d| <= 0.5
    r = jnp.where((d == 0.5) & (lo > 0), r + 1.0, r)
    return jnp.where((d == -0.5) & (lo < 0), r - 1.0, r)


def reduce_min(x, axis):
    hi, lo = x
    mh = hi.min(axis=axis, keepdims=True)
    ml = jnp.where(hi == mh, lo, jnp.inf).min(axis=axis, keepdims=True)
    return mh.squeeze(axis), ml.squeeze(axis)


def reduce_max(x, axis):
    hi, lo = x
    mh = hi.max(axis=axis, keepdims=True)
    ml = jnp.where(hi == mh, lo, -jnp.inf).max(axis=axis, keepdims=True)
    return mh.squeeze(axis), ml.squeeze(axis)


def argmin(x, axis):
    """First index of the minimum (``jnp.argmin`` tie semantics)."""
    mh, ml = reduce_min(x, axis)
    hit = (x[0] == jnp.expand_dims(mh, axis)) \
        & (x[1] == jnp.expand_dims(ml, axis))
    return jnp.argmax(hit, axis=axis)


def argmax(x, axis):
    """First index of the maximum (``jnp.argmax`` tie semantics)."""
    mh, ml = reduce_max(x, axis)
    hit = (x[0] == jnp.expand_dims(mh, axis)) \
        & (x[1] == jnp.expand_dims(ml, axis))
    return jnp.argmax(hit, axis=axis)
