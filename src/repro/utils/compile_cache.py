"""JAX's persistent compilation cache at one fixed place.

Called from the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``), never at import: turning the cache on is a decision of
the program that owns the process.
"""
from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, so
    nothing else is set); otherwise ``<repo>/.jax_cache``, which git
    ignores. The path never depends on a temporary name, the pid or the
    time, so a later run finds what an earlier one stored.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
