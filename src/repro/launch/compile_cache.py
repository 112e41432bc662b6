"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, so
nothing is set here), else ``.jax_cache/`` at the root of the checkout: a
fixed path, so the next run from the same checkout finds what this one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
