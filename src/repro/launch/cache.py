"""Where compiled programs are cached across processes.

``setup_compile_cache()`` runs before an entry point's first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else is
set here.  Otherwise the cache goes to one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the cache key
includes the path, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
