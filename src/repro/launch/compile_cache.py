"""Persistent compilation cache, placed from outside the program.

``enable_compile_cache()`` is called by every entry point before its first
compile.  The cache directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and otherwise the fixed ``<checkout>/.jax_cache`` (listed in
``.gitignore``).  The path is part of what a later run must find again, so
it never depends on a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
