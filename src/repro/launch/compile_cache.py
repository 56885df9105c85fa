"""Where JAX's persistent compilation cache lives for this checkout.

The entry points call :func:`enable_compile_cache` from ``main()``, never
at import, so a test that imports them leaves the process's cache alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout: a cache that moves between runs (a
# temporary name, a process id, the time) is never hit again.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this function sets nothing; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
