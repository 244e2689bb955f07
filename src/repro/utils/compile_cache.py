"""JAX's persistent compilation cache, placed for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call ``enable()``
before their first compile; importing ``repro`` never touches the cache.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at the fixed
``<repo>/.jax_cache``: the directory is part of the cache key, so a path
built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
