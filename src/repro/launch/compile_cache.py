"""JAX's persistent compilation cache for the launchers and the chip smoke.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, since the directory is part of what a later run must find.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
