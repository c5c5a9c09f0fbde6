"""Persistent XLA compilation cache for the entry-point scripts.

Called by ``chip_smoke.py``, ``benchmarks/run.py`` and the
``benchmarks/kernelbench.py`` main, never on library import: a library
that picks a cache directory for its importer would override the
importer's own choice.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root (this file is <root>/src/repro/compile_cache.py)
_ROOT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps the
    cache; it is left as it is.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  The path is fixed (no temp name, pid or
    time in it) so that a later run of the same checkout finds the entries
    an earlier run wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
