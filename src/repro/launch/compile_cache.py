"""JAX's persistent compilation cache for the entry points.

``serve``, ``repro.api`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, so a second run
of the same programs on the same machine loads them instead of compiling.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

# src/repro/launch/compile_cache.py -> the checkout root. A fixed path: the
# cache directory is part of each entry's key, so a path that moved between
# runs (a temp name, a pid, a time) would never hit.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; a directory already configured in-process also
    stands. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
