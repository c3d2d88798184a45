"""JAX's persistent compilation cache, at one fixed place per checkout.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing is
set here.  Otherwise the cache lives in ``<checkout>/.jax_cache``, a fixed path, so
that a second run of the same program finds what the first one compiled.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
