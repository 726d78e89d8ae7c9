"""Placement of JAX's persistent compilation cache for entry points.

A cache hit needs the same directory on every run (the path is part of
the cache key), so the directory is either given from outside or fixed
inside the checkout — never a temporary, per-process or per-run path.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``artifacts/jax_cache`` of this checkout (``artifacts/`` is gitignored)
CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts", "jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile.  Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
