"""Persistent XLA compile cache shared by the repo's entry points.

Every entry point (chip_smoke.py, examples/*.py, the benchmark scripts,
repro.launch.train) calls `enable()` before it compiles anything, so a
second process on the same checkout loads its programs instead of
compiling them again. Tests never call it: several of them measure cold
compiles.

Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at the fixed `<checkout>/.jax_cache`
(the directory is part of what makes a later run hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> Path:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return Path(os.environ[ENV])
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
