"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so it must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself), and otherwise the fixed ``<repo>/.jax_cache``.  Entry points
call :func:`use_compile_cache` before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    Takes effect only before the process's first compile: JAX settles its
    cache once.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
