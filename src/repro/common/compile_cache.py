"""Where jax keeps its persistent compilation cache.

Called by the command-line entry points, never on import, so library
users and tests keep jax's own default (no cache).
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, because the cache key includes
# it and a directory that moves never hits
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names (jax reads it itself), else
    ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
