"""JAX persistent compilation cache location for the entry points.

Called from an entry point's ``main()``, never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of the cache key, so a directory
that moved (temp, pid or time-derived) would never hit.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that location."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
