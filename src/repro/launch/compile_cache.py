"""Persistent compilation cache, placed from outside the program.

A full-width serving step compiles for tens of seconds per shape and warmup
compiles every bucket, so entry points that run on a chip keep JAX's
persistent cache. Call ``use_compile_cache()`` once at start-up, before the
first compile; importing ``repro`` never does it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"

# The checkout root (src/repro/launch/ -> three levels up). A fixed path:
# the cache directory is part of what a later run must find again.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and nothing
    is set here. Otherwise the cache is ``.jax_cache/`` in the checkout.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
