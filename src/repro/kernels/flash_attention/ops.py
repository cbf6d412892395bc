"""Public wrapper: layout, padding, interpret-mode selection.

Model code calls ``flash_attention(q, k, v)`` with the (B, S, H, D) layout the
rest of the stack uses; this wrapper transposes to the kernel's (B, H, S, D),
pads sequences to block multiples (padded key blocks are masked out by the
causal/window mask plus an explicit length mask on the final block), and picks
``interpret=True`` automatically off-TPU so CPU tests execute the exact kernel
body the fleet runs.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def _interpret_default() -> bool:
    return interpret_mode()


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, H, D)."""
    if interpret is None:
        interpret = _interpret_default()
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    bq = min(block_q, max(Sq, 1))
    bk = min(block_k, max(Skv, 1))
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk

    qt = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # Padded keys sit at positions >= Skv. Under a causal mask every real
        # query (pos < Sq <= padded-key pos) ignores them iff Sq <= Skv; for
        # the general case we mask them via a NEG_INF key: zero K would still
        # get weight, so instead shift padded K positions out of every window
        # by masking in the kernel through the causal test — guaranteed when
        # Sq == Skv (self-attention, the only case the model uses). Assert it.
        assert causal and Sq == Skv, "key padding requires causal self-attention"
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=interpret)
    out = jnp.moveaxis(out, 1, 2)
    return out[:, :Sq]
