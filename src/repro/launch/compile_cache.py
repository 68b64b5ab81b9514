"""JAX's persistent compilation cache for the launchers and the chip smoke.

A cold TPU process spends minutes compiling a full-size step; the cache
lets the next process with the same programs load them instead.  Its key
includes the directory, so the directory is fixed: the one JAX is given in
``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise
``<checkout>/.jax_compile_cache`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Switch the cache on before the first compile; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and no
    other directory is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
