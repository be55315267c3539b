"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` once, before their first compile;
importing this module changes nothing. When ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here. Otherwise the cache goes
to ``.jax_cache`` at the root of the checkout: a fixed path, never a
temporary name, so that the next run from the same checkout finds the
programs this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
