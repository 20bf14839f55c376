"""JAX's persistent compilation cache, switched on by the entry points.

A cold process compiles every program it runs, and on a TPU that is a
large share of a short run. The entry points (`chip_smoke.py`,
`repro.launch.serve`, `repro.launch.train`, `benchmarks/run.py`) call
`enable_compile_cache()` once, before their first compile, so later
processes on the same checkout read the compiled programs back. Library
code never calls it: importing `repro` changes no jax configuration.
"""
from __future__ import annotations

import os

import jax

#: where the cache lives when JAX_COMPILATION_CACHE_DIR is unset: a fixed
#: directory of the checkout, so the next process finds what this one
#: wrote (a per-process or temporary directory is gone by then).
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already reads that directory
    and nothing else is configured here; otherwise the cache goes to
    `DEFAULT_CACHE_DIR` (``<checkout>/.jax_cache``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
