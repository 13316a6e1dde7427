"""JAX's persistent compilation cache for the entry points.

Each driver's ``main()`` calls :func:`enable_compile_cache` before it
compiles anything; importing a module never turns the cache on.  The
directory is fixed, because it is part of what a later run has to find
again: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, so nothing else is set), else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
