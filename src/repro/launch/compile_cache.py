"""JAX's persistent compilation cache for every entry point that compiles
the model (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``).

A compile cache only hits when its directory stays put, so it is either
the one the environment names in ``JAX_COMPILATION_CACHE_DIR`` (which JAX
reads by itself) or a fixed directory in the repository checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where compiled programs are kept: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<repo>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  A set
    ``JAX_COMPILATION_CACHE_DIR`` is left to JAX: nothing is configured
    in code."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
