"""Where JAX keeps its persistent compilation cache.

The entry points (`chip_smoke.py`, `repro.launch.serve`,
`repro.launch.train`, `benchmarks/run.py`) call `use_compile_cache()`
once, before their first compile; importing this module changes
nothing. The cache directory is part of what a cached program is found
by, so it is a fixed path and never a temporary name.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fallback: `.jax_cache/` at the root of the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. When
    `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to `DEFAULT_DIR`."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
