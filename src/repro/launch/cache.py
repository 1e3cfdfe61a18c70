"""Where JAX keeps its persistent compilation cache.

A cold process on a TPU spends minutes compiling; the persistent cache
turns the second run of the same program into a lookup. The cache key
includes the directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set -- JAX reads it itself; nothing else is
  set, so a deployment that provides a shared cache keeps it.
* unset -- a fixed directory inside the checkout (``.jax_cache/``, listed
  in ``.gitignore``). Never a temporary name, a process id or a time.

Call :func:`configure_compilation_cache` once per process, before the first
compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/cache.py -> the checkout root, three levels above src/.
CHECKOUT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                      / ".jax_cache")


def configure_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory (the environment's, when it names one)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
