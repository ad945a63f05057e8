"""JAX's persistent compilation cache, shared by the entry points.

The launchers (``launch/serve.py``, ``launch/train.py``) and ``chip_smoke.py``
call :func:`enable_compile_cache` once, before their first compile, so later
processes on the same machine reuse compiled programs instead of compiling
from cold.  Importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/runtime/compile_cache.py → the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
    fixed path (never a temporary name, a pid or a time), since a cache that
    moves between runs never hits.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
