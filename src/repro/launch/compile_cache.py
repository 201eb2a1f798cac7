"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the cache directory, so a path that moves between
runs (a temp dir, a pid or a timestamp in it) never hits.  Every entry
point that compiles for the chip calls :func:`enable_compile_cache` once,
before its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — src/repro/launch/ is three levels below the root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives in the checkout's
    git-ignored ``.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
