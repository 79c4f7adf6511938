"""Where JAX keeps its persistent compilation cache for the entry points.

``chip_smoke.py`` and ``bench/run.py`` call :func:`use_compile_cache`
before their first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache lives at one
fixed, git-ignored path inside the checkout, ``<repo>/.jax_cache``: a
cache directory that moves between runs never hits, so the path is never
built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
