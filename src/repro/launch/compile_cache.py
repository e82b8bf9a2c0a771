"""Where JAX keeps its persistent compilation cache.

The cache's directory is part of what makes an entry hit, so it must not
move between runs: `$JAX_COMPILATION_CACHE_DIR` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), else one
fixed, git-ignored directory inside the checkout.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
