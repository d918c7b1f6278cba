"""JAX's persistent compilation cache, at one fixed place.

Entry points (``drl_control``, ``serve_control``, the benchmark CLIs and
``chip_smoke.py``) call :func:`enable_compile_cache` when run as a
script, before their ``main``; importing this module, or calling a
``main`` in-process, changes nothing.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says when that is set (jax reads the
variable itself, so no path is configured), and otherwise in
``<checkout>/.jax_cache``.  A later run finds only what an earlier run
wrote to the same path, so it must not move between runs: never a
temporary, per-process or dated one.

The cache key holds the programs' metadata too.  JAX leaves it out by
default, and a program that differs from a cached one only in its
``jax.named_scope``s would then load that executable, whose ``op_name``s
are the other lowering's (``diagnostics.scope_tables`` reads them).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the variable's value, else the fixed
    in-checkout path."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on (see module doc); returns its path."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
