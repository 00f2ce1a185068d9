"""JAX's persistent compilation cache, at one fixed place per checkout.

A 24-layer step takes minutes to compile for a TPU; the cache lets a
second run on the same checkout load the compiled programs instead.  The
cache key includes the directory, so it must not move between runs: it
lives at ``<checkout>/.jax_cache`` (resolved from this file's location,
listed in ``.gitignore``), never under a temp name, pid or time.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this module sets no other directory.

Called by the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``), never at ``import repro``: a library import must not
change the process's JAX configuration.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
