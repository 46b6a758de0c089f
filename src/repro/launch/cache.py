"""JAX's persistent compilation cache, placed from outside the program.

A cache hit needs the same directory on the next run, so the directory is
either the deployment's own (``$JAX_COMPILATION_CACHE_DIR``, which JAX reads
by itself) or one fixed path inside the checkout — never one built from a
temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set (no other directory is
    set then), else ``DEFAULT_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
