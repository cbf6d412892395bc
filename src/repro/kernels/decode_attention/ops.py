"""Public wrapper for the flash-decode kernel: layout + padding + interpret."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import decode_attention_bhd


def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 256,
                     interpret: bool | None = None):
    """q: (B, 1, H, D); caches: (B, S, Hkv, D); lengths: (B,) -> (B, 1, H, D)."""
    if interpret is None:
        interpret = interpret_mode()
    B, _, H, D = q.shape
    S = k_cache.shape[1]
    bk = min(block_k, S)
    pad = (-S) % bk
    kt = jnp.moveaxis(k_cache, 2, 1)  # (B, Hkv, S, D)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qt = jnp.moveaxis(q, 2, 1)  # (B, H, 1, D)
    out = decode_attention_bhd(qt, kt, vt, lengths, block_k=bk,
                               interpret=interpret)
    return jnp.moveaxis(out, 1, 2)  # (B, 1, H, D)
