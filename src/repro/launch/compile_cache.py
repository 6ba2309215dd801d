"""Placement of JAX's persistent compilation cache for the entry points.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
used as is (JAX reads it itself) and nothing else is configured here;
otherwise the cache lives at a fixed ``.jax_cache/`` under the checkout
root.  Entry points call :func:`place_compile_cache` from ``main()``
before their first compile — never at import.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def place_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
