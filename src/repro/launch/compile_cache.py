"""Where JAX keeps compiled programs between processes.

Call :func:`use_compile_cache` once at the top of an entry point (never at
import).  A cache directory set from outside through
``JAX_COMPILATION_CACHE_DIR`` wins and is left alone; otherwise the cache
lives at ``<checkout>/.jax_cache``.  The path is fixed because it is part
of the cache's key: a directory named after a pid, a temporary name or
the time would never be hit again.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside  # jax read the variable itself at import
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
