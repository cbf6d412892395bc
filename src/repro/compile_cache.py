"""Where JAX keeps its persistent compilation cache.

Every entry point that may touch an accelerator calls ``configure()`` first,
before its first compile. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing else is set here. Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the directory is part of the
cache key — a path built from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
