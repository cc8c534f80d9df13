"""JAX's persistent compilation cache for the repo's entry points.

Every serve shape ``(B, npad)`` is its own compiled program, and a cold
process compiles them all again.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/*``) call :func:`enable_compile_cache`
once at start-up; library modules never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and the
cache stays there.  Otherwise the cache lives at one fixed path in the
checkout, ``<repo>/.jax_cache``: the directory is part of each entry's
key, so a moving (temporary, per-process) directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # serve-shape compiles take well under JAX's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
