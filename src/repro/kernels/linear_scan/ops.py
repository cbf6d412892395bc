"""Public wrapper for the RG-LRU linear scan kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.linear_scan.kernel import linear_scan_bsd


def linear_scan(x, a, *, chunk: int = 256, interpret: bool | None = None):
    """x, a: (B, S, D). Returns (h (B, S, D) fp32, final_state (B, D) fp32).

    Tail padding uses (a=1, x=0): the state passes through unchanged.
    """
    if interpret is None:
        interpret = interpret_mode()
    B, S, D = x.shape
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    y, state = linear_scan_bsd(x, a, chunk=Q, interpret=interpret)
    return y[:, :S], state

