"""Where JAX's persistent compilation cache lives.

Entry points call :func:`configure_compile_cache` once, before their
first compile.  The cache directory is part of every entry's key, so it
must not move between runs: either the environment names it, or it is
one fixed directory inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIRNAME", "configure_compile_cache"]

CACHE_DIRNAME = ".jax_cache"


def configure_compile_cache(root: str) -> str:
    """Place the persistent compile cache; returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and no
    directory is set here.  Otherwise the cache goes to
    ``<root>/.jax_cache`` — ``root`` is the checkout the entry point
    runs from, never a temporary or per-process name.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
