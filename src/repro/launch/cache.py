"""JAX's persistent compilation cache for the command-line entry points.

Each CLI ``main`` (and ``chip_smoke.py``) calls ``enable_compile_cache``
once, before its first compile; importing this module changes nothing.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
there and this leaves it alone.  Otherwise the cache goes to a fixed
directory inside the checkout (``.jax_cache``, git-ignored): the path is
part of what a later run must find, so it never moves.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # tuning trials compile one short kernel each: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
