"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only when the cache directory is the
same, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing here
overrides it), else ``<checkout>/.jax_cache``.  Entry points that compile
for the device call ``enable_compile_cache()`` before their first jit.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
