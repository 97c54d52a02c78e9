"""JAX persistent compilation cache at a fixed location.

Entry points (``serve.main``, ``chip_smoke.py``) call
:func:`enable_compilation_cache` first thing; nothing calls it at import or
from the tests.  The cache key includes the directory, so it lives at a path
that never moves: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), else ``<repo>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compilation_cache", "DEFAULT_CACHE_DIR"]

#: ``<repo>/.jax_cache`` — this file sits at ``<repo>/src/repro/launch/``.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
