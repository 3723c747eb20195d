"""JAX's persistent compilation cache, kept at one fixed place.

A fresh process compiles every program it runs, and one decode step of a
4096-wide model takes tens of seconds to compile.  The cache's entries are
only found again under the same directory, so the directory is never a
temporary name: it is ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable"]

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation.  Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
