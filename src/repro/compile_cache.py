"""Where JAX keeps its persistent compilation cache.

A cold train step of GPT-2 345M compiles for over a minute, and every
process that runs the same program pays it again unless the compiled
executable is found on disk.  The cache key includes the directory, so the
directory must not move between runs.

The key also includes each program's metadata (op names, source
locations).  A ``jax.named_scope`` changes nothing else, so under JAX's
default key, which leaves metadata out, a program would load an executable
compiled from the same HLO under other names, and its profiles would carry
those names instead of its own.  The price: moving traced source lines
compiles once more.
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
    (JAX reads it itself; no directory is set), else :data:`DEFAULT_DIR`.
    Keys include the programs' metadata (module docstring)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
