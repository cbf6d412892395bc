"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Meshes:
- single-pod: (16, 16) = ("data", "model") — 256 chips (one v5e pod);
- multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips. The "pod"
  axis composes with "data" for gradient reduction; all cross-pod traffic is
  the DP all-reduce (optionally compressed, repro.distributed.compression).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding rules place arrays with with_sharding_constraint,
    # which rejects the Explicit axes jax.make_mesh now defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU dev box: 1 device) — smoke tests/examples."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))
