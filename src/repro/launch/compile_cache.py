"""JAX's persistent compilation cache for this repository's entry points.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable` once at start-up; importing this
module sets nothing.  The cache lives where ``JAX_COMPILATION_CACHE_DIR``
says when that is set, and otherwise in one fixed directory inside the
checkout: the directory is part of what a cached program is keyed on, so a
path that changed from run to run would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
