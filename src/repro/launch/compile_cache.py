"""Where JAX's persistent compilation cache lives, decided from outside the code.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here sets
another directory. Otherwise the cache goes to ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because a cache whose directory moves from run to run
is never hit. No path is built from a temporary name, a process id or the time.

This module imports JAX only inside :func:`enable`, so a parent process that must
stay off the accelerator (``benchmarks/run.py``) can read :func:`cache_dir` and hand
it to its children through the environment.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the compilation cache uses in this checkout."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent cache on (at :func:`cache_dir`); returns its path."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
