"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and stands.
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path, so
that a later run finds what an earlier one wrote, which a path built from
a temp name, a pid or the time would never do.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
