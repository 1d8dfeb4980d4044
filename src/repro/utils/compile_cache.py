"""JAX persistent compilation cache at a fixed path.

A TPU program of this repo takes a minute or more to compile cold, and the
cache key includes the cache directory: a directory that moves between runs
never hits. So entry points call :func:`enable_compile_cache` before their
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/src/repro/utils/compile_cache.py -> <repo>/.jax_cache
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent compilation cache on.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache`` at the
    root of the checkout (git-ignored).
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
