"""Two-float arithmetic (``repro.kernels.dfloat``) against float64.

The TPU branch of the placement core decides with these operations, so
each is checked where f32 alone would get the float64 answer wrong: sums
and products below f32 resolution, comparisons of values equal in f32,
round-half-even at x.5 ± tiny, and the ±inf sentinels of empty pool slots.
"""

from __future__ import annotations

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.kernels import dfloat  # noqa: E402


def dev(x):
    hi, lo = dfloat.split(np.asarray(x, np.float64))
    return jnp.asarray(hi), jnp.asarray(lo)


def host(v):
    return dfloat.join(*v)


@pytest.fixture
def pairs(rng):
    a = rng.uniform(-1e8, 1e8, 512) + rng.uniform(0, 1, 512)
    b = rng.uniform(-1e3, 1e3, 512) * 10.0 ** rng.integers(-9, 3, 512)
    return a, b


@pytest.mark.parametrize("op,ref", [
    (dfloat.add, np.add), (dfloat.sub, np.subtract),
    (dfloat.mul, np.multiply)])
def test_arithmetic_tracks_float64(pairs, op, ref):
    a, b = pairs
    got = host(op(dev(a), dev(b)))
    want = ref(a, b)
    assert np.all(np.abs(got - want) <= 2.0 ** -44 * np.abs(want) + 1e-30)
    # and f32 alone cannot: the rounding of an f32 result is far coarser
    f32 = ref(a.astype(np.float32), b.astype(np.float32)).astype(np.float64)
    assert np.max(np.abs(f32 - want) / np.abs(want)) > 1e-9


def test_comparisons_resolve_values_equal_in_f32():
    base = 2.6e8
    a = np.array([base, base + 1e-3, base - 1e-3, base])
    b = np.array([base + 1e-3, base, base, base])
    assert np.all(a.astype(np.float32) == b.astype(np.float32))
    x, y = dev(a), dev(b)
    np.testing.assert_array_equal(np.asarray(dfloat.lt(x, y)), a < b)
    np.testing.assert_array_equal(np.asarray(dfloat.le(x, y)), a <= b)
    np.testing.assert_array_equal(np.asarray(dfloat.gt(x, y)), a > b)
    np.testing.assert_array_equal(np.asarray(dfloat.eq(x, y)), a == b)
    np.testing.assert_array_equal(
        host(dfloat.maximum(x, y)), np.maximum(a, b))


@pytest.mark.parametrize("v", [2.5, 3.5, 2.5 + 1e-9, 2.5 - 1e-9, 3.5 - 1e-9,
                               3.5 + 1e-9, 1234.5, 1234.5 + 2e-10, 0.49999,
                               -1.5, 7.0])
def test_round_half_even_matches_numpy(v):
    assert float(dfloat.round_half_even(dev(v))) == float(np.round(v))


def test_infinities_survive_add_and_reductions():
    x = dev([np.inf, -np.inf, 5.0])
    s = dfloat.add(x, dev([1e-3, 7.0, -np.inf]))
    np.testing.assert_array_equal(host(s), [np.inf, -np.inf, -np.inf])
    assert not np.any(np.isnan(np.asarray(s[1])))
    v = dev([[3.0, -np.inf, 3.0 + 1e-12, 1.0]])
    assert int(dfloat.argmax(v, 1)[0]) == 2
    assert int(dfloat.argmin(v, 1)[0]) == 1
    np.testing.assert_array_equal(host(dfloat.reduce_min(v, 1)), [-np.inf])
