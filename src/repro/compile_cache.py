"""Persistent XLA compilation cache: one location per checkout.

``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at
``<repo>/.jax_cache`` — anchored to the repo root like the calibration file
(``repro.core.calibration``), never to the cwd, a temporary name, a process
id or a time, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
