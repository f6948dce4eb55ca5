"""Where JAX keeps its persistent compilation cache.

Call `use_persistent_cache()` once, before the first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and nothing
else is set. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the cache directory is part of what a
later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the many sub-second compiles of a run add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
