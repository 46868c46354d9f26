"""Persistent XLA compile cache for the program's entry points.

Cold compiles are a large part of a fresh process's first queries (a
2^20-row int64 argsort alone compiles for about a minute on a TPU), so
entry points keep compiled programs on disk.  Importing the library never
touches this: ``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache"]

#: the checkout this package lives in (``<checkout>/src/repro/...``)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
