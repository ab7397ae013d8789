"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call ``use_compile_cache()`` once at start-up; nothing calls it at import.
The cache's path is part of what it keys on, so it never moves: either the
directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, and no directory is set in code) or ``.jax_cache/`` at the root
of this checkout.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    # decode kernels compile in about a second each: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
