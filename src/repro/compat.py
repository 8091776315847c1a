"""Platform helpers shared by every entry point.

* ``on_tpu``: the one backend probe.  Kernel call sites pick Pallas
  interpret mode from it; a backend that fails to start raises instead of
  reading as "no TPU", so a broken chip start never runs the interpreter.
* ``enable_compile_cache``: JAX's persistent compilation cache, placed by
  ``JAX_COMPILATION_CACHE_DIR`` when it is set and otherwise at a fixed
  ``.jax_cache/`` in the checkout (the path is part of the cache key, so it
  must not move between runs).
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/compat.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; only when it is unset
    does this point the cache at ``DEFAULT_CACHE_DIR``."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
