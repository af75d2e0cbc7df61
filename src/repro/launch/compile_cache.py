"""JAX's persistent compilation cache, kept at one fixed place.

The cache's directory is part of its key, so it must not move between
runs: either where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that
variable itself) or ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Call it
    before the first compile: JAX settles on a cache at that point."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
