"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples'
``main()``) call :func:`enable_compile_cache` before their first compile,
so a second run of any of them loads the compiled programs instead of
compiling them again.  Library code never calls it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so every later run of this checkout finds what earlier runs wrote
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``
    (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
