"""JAX persistent compilation cache at a fixed path.

A cold process on the chip compiles every search plan from scratch; the
persistent cache lets the next process of the same checkout reuse them.
The cache key includes its directory, so the directory must not move
between runs: it is `JAX_COMPILATION_CACHE_DIR` when that is set (JAX reads
it itself and nothing here overrides it), else `.jax_cache/` at the root of
the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
