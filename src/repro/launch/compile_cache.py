"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once, before their first
compile; nothing calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it on its own and this sets nothing.  Otherwise the
cache is ``.jax_cache/`` at the root of the checkout: a fixed path, so a
second run in the same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
