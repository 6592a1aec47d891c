"""Persistent XLA compilation cache for the command-line entry points.

Call :func:`enable_compile_cache` first thing in the ``main()`` of a
script that runs the device path; never on import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and it wins.
Otherwise the cache lives at one fixed directory inside the checkout
(listed in ``.gitignore``): a path built from a temp name, a pid or the
time would never be found again by the next process.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
