"""Where the entry scripts keep JAX's persistent compilation cache.

The cache key includes the directory, so the directory must not move between
runs: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here; otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout. Scripts call ``use_compile_cache()`` first thing in
``main``; importing the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on -> the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
