"""JAX's persistent compilation cache, placed from outside the program.

Entry points (the launch CLIs' ``main()`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before anything compiles; importing this
module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and no other directory is configured here. Otherwise the
cache lives at a fixed directory inside the checkout (``.jax_cache/``,
listed in ``.gitignore``): the directory is part of the cache key, so a
path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir               # jax reads the variable on its own
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
