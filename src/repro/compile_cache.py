"""Where JAX keeps its persistent compilation cache.

A cold train step of GPT-2 345M compiles for over a minute, and every
process that runs the same program pays it again unless the compiled
executable is found on disk.  The cache key includes the directory, so the
directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, so a second run in the same checkout hits
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself; nothing else is set), else :data:`DEFAULT_DIR`."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
