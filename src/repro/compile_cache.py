"""JAX's persistent compilation cache for this repo's entry points.

Every power-of-two pad size of the simulator's device scan is its own
compile, and the large ones take seconds each, so entry points
(``chip_smoke.py``, ``benchmarks/*.py``) call :func:`enable_compile_cache`
before their first compile. ``JAX_COMPILATION_CACHE_DIR`` wins when it is
set (JAX reads it itself); otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` — the directory is part of what makes a cache
hit, so it never depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under JAX's default 1 s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
