"""Where JAX keeps its persistent compile cache for the chip entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured (JAX reads it itself)
and nothing else is configured.  Otherwise the cache goes to one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (gitignored).  The
directory is part of what a later run must find again, so it is never
derived from a tempdir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory (the env
    var's, else :data:`DEFAULT_DIR`) and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
