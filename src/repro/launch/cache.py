"""Persistent compilation cache for the repo's entry points."""
from __future__ import annotations

import os

import jax


def use_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache lives at the fixed
    ``.jax_cache/`` of ``checkout`` (the repo root, listed in
    ``.gitignore``): the path is part of the cache's key, so it never
    depends on a temporary name, a process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(os.fspath(checkout)), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
