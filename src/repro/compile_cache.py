"""Persistent XLA compilation cache for the repo's entry points.

``enable()`` is called once by each entry point (``chip_smoke.py``,
``bench/run.py``, ``examples/*``) before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at a fixed path inside the checkout
(``.jax_cache/``, git-ignored): the path is part of the cache key, so a
fixed one lets every later run from the same checkout hit it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
