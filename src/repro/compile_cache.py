"""Persistent XLA compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, the benchmarks, the examples) call
:func:`setup_compile_cache` before their first compile, so a second run on
the same checkout skips recompiling every program.  Library import and the
tests never call it.

The cache path is part of the cache's key, so it is fixed: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable itself and this
helper sets nothing; otherwise the cache lives in ``<checkout>/.jax_cache``
(git-ignored), never in a temporary or per-process directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
