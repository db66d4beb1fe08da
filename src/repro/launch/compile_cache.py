"""JAX's persistent compilation cache for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at `<checkout>/.jax_cache`: a
fixed path, since the directory is part of what a later run looks up, so a
name made from a temp dir, a pid or the time would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process: JAX settles its
    cache when it first compiles."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
