"""JAX persistent compilation cache, shared by every entry point.

Call :func:`enable_compile_cache` once at the start of a program (never at
import).  ``JAX_COMPILATION_CACHE_DIR``, when set, is the only directory
used; otherwise the cache lives at a fixed ``<checkout>/.jax_cache`` (the
path is part of the cache key, so it must not move between runs).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
