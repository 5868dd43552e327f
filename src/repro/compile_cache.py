"""Where JAX's persistent compilation cache lives.

Processes of one run that compile the same programs (the chip smoke test,
benchmark modules) share compiled code through this cache.  The path is
part of what a cache entry is found by, so it never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets one, and
otherwise ``.jax_cache/`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        # JAX reads the variable itself; set no other path
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
