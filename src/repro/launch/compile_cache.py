"""JAX's persistent compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX reads it by
itself. Otherwise the cache is the fixed ``.jax_cache`` directory at the root
of the checkout: the path is part of each entry's key, so it never moves.
Entry points call :func:`enable_compile_cache` from ``main``, never at import.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory it lives in."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
