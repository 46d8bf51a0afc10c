"""JAX's persistent compilation cache for this checkout's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it. Otherwise the cache lives at ``<checkout>/.jax_cache``: one
fixed path, because the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
