"""Where JAX's persistent compilation cache lives.

The entry points that compile the serving steps (``launch/serve``,
``launch/autotune``, ``chip_smoke.py``, ``chipbench``) call
:func:`enable_compile_cache` once, before their first compile, so a
second process on the same machine reuses the executables of the first.

The cache key includes the directory, so the directory must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads that variable itself; nothing is set in code) or the fixed
``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root, four levels up
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
