"""JAX's persistent compilation cache, placed from outside the program.

Entry points that run on the chip call `enable_compile_cache()` once,
before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing else is configured.  Otherwise the
cache goes to one fixed directory inside the checkout: the path is part
of what a later run looks up, so it is never built from a temporary
name, a pid or the time.  Tests do not call this.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
