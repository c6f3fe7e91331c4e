"""JAX persistent compilation cache for the repo's entry points.

Importing this module changes nothing; an entry point (`chip_smoke.py`,
`benchmarks/run.py`) calls `enable_compile_cache()` once, before its
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache key includes nothing of
# the path, but a directory that moves between runs never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here; otherwise the cache lives at
    `<checkout>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
