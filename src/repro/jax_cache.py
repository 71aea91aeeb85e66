"""JAX's persistent compilation cache, kept at one fixed place.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it at import and this
module sets no other directory. Otherwise the cache lives in `.jax_cache/`
at the repository root: the cache key includes the directory, so a path
that moved between runs (temporary, pid- or time-named) would never hit.

The policy's programs compile in well under JAX's default one-second
threshold, so the threshold is dropped to 0 or nothing would be cached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Call before the
    first compile of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
