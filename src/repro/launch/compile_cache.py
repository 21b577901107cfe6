"""JAX's persistent compilation cache, kept at a fixed directory.

A cache directory that moves between runs never hits, so the directory
is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it and
``<checkout>/.jax_cache`` otherwise.  Entry points call
``enable_compile_cache()`` at the top of ``main``; importing a library
module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache directory: ``.jax_cache`` at the root of the checkout.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this changes nothing: JAX
    reads the variable itself.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
