"""JAX's persistent compilation cache, placed from outside or in the
checkout.

A run on a fresh machine compiles everything; a later run that can see
the same cache directory loads the compiled programs instead.  The
directory is part of what makes an entry findable, so it is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable where the machine
sets it, else ``.jax_compile_cache`` at the root of the checkout (listed
in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / \
    ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Call before the first compile.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX has read it already and no other directory is set.
    Every entry is kept, however fast it compiled: the conv kernels
    compile in about a second, under JAX's default threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
