"""Persistent XLA compilation cache for the entry points.

A cold TPU compile of a full train or serve step takes tens of seconds to
minutes; JAX's persistent cache lets the next process (or the next call on
the same machine) skip it. The cache key includes the directory, so the
directory must not move between runs: never a temporary, per-process or
time-stamped path.

The drivers (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``python -m repro.serving``, ``benchmarks/run.py``) call :func:`enable`
once at start-up; library code and tests do not.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache — this file lives at <repo>/src/repro/compile_cache.py.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set. Otherwise the cache goes to ``<repo>/.jax_cache``
    (listed in ``.gitignore``)."""
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
