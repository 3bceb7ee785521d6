"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only where the cache directory is the
same from one process to the next, so the directory never comes from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <repo>/src/repro/launch/compile_cache.py → <repo>/.jax_cache
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache goes to ``.jax_cache/``
    at the repository root.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
