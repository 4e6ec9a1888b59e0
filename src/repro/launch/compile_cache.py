"""JAX's persistent compilation cache, for the entry points' ``main``.

A cold full-width run compiles every (batch, bucket, table-width) shape
of every stage; the cache lets a later run of the same checkout load
them instead.  Called from ``main`` only, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout: the path is part of the cache key, so a
# directory that moves between runs never hits (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no
    other directory is configured here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
