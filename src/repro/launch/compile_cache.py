"""Persistent XLA compilation cache for the runnable entry points.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing ``repro`` never does.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is changed.  Otherwise the cache goes to the fixed
directory ``<repo>/.jax_cache``: the directory is part of what a later
process must find again, so it is never built from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)
