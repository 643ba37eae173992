"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``python -m repro.scenarios``, the
engine benchmark) call :func:`enable` once, before their first
compile; importing a library module never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the path is part of what makes a
later run find its entries again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
