"""JAX's persistent compilation cache for the repo's entry points.

``launch.train``, ``launch.serve`` and ``chip_smoke.py`` call ``enable()``
before they compile anything. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it by itself and nothing is changed here. Otherwise the cache
lives at one fixed directory inside the checkout, ``<repo>/.jax_cache``
(listed in ``.gitignore``): the directory is part of what JAX keys an entry
on, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_DIR, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
