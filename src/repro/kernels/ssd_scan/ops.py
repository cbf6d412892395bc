"""Public wrapper for the SSD kernel: model layout, padding, interpret."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_bhsd


def ssd(x, dt, A, B, C, *, chunk: int = 128, interpret: bool | None = None):
    """Model layout in/out: x (b, S, nh, hd); dt (b, S, nh) fp32; A (nh,);
    B/C (b, S, ds). Returns (y (b, S, nh, hd), final_state (b, nh, hd, ds)).

    Zero-padding the tail chunk is inert: dt=0 ⇒ decay exp(0)=1 and zero input
    contribution, so the carried state passes through padded steps unchanged.
    """
    if interpret is None:
        interpret = interpret_mode()
    b, S, nh, hd = x.shape
    Q = min(chunk, S)
    pad = (-S) % Q
    xt = jnp.moveaxis(x, 2, 1)               # (b, nh, S, hd)
    dtt = jnp.moveaxis(dt, 2, 1)             # (b, nh, S)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, state = ssd_scan_bhsd(
        xt, dtt.astype(jnp.float32), A.astype(jnp.float32), B, C,
        chunk=Q, interpret=interpret)
    y = jnp.moveaxis(y, 1, 2)[:, :S]          # (b, S, nh, hd)
    return y, state
