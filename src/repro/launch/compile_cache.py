"""Persistent XLA compile cache for the entry points.

Every entry point (the ``launch/*.py`` mains, ``chip_smoke.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` once, before its
first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set — jax already reads it; nothing else is
    configured here, so the cache lands there and nowhere else;
  * otherwise the cache goes to the fixed path ``<checkout>/.jax_cache`` (the
    path is part of the cache key, so it never names a temporary directory,
    a PID or a time);
  * ``JAX_ENABLE_COMPILATION_CACHE=false`` turns it off (tests that start an
    entry point in a subprocess do this).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Point jax's persistent compile cache at its directory; returns that
    directory, or None when the cache is disabled."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
