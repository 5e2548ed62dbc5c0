"""JAX's persistent compilation cache, kept at one fixed place.

A cold process on the chip spends minutes compiling; the persistent
cache lets the next process with the same programs skip that.  The
cache key includes the directory, so the directory must not move.

Called from entry points' ``main()`` (never at import: tests and worker
processes import the launchers).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing
    is set in code (JAX reads the variable itself); otherwise the cache is
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
