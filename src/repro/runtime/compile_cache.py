"""JAX's persistent compilation cache, kept at one fixed place.

A cold 32-layer program takes tens of seconds to compile; with the cache on,
the next process that builds the same program loads it instead.  The cache
directory is part of what JAX keys an entry on, so it never moves:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), ``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Entry points call it from ``main``, never at import."""
    if os.environ.get(CACHE_DIR_ENV):
        return os.environ[CACHE_DIR_ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
