"""JAX's persistent compilation cache for the repo's entry points.

A cold run of the served cascade at full width compiles for minutes; the
cache lets the next process load those programs instead. Entry points
call ``enable_compile_cache()`` before their first compile. Importing
the library turns nothing on, so tests compile as they always did.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here. Otherwise the cache is ``.jax_cache/`` at the root of
    the checkout: a fixed path, so the next process looks where this one
    wrote."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
