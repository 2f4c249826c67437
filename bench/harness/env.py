"""Environment JAX reads when it is first imported; set before that."""

from __future__ import annotations

import os


def jax_env(root: str, rehearsal: bool) -> None:
    """The persistent compilation cache in the checkout, with every program
    cached however quick its compile, so that only a checkout's first run
    compiles; and, for a rehearsal, the CPU."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no eviction: an evicting cache needs an access-time file beside every
    # entry, and one written without it makes every later write fail
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
