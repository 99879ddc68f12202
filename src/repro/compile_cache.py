"""JAX's persistent compilation cache, at a directory that can be set from outside.

`enable()` is called by the entry points (``chip_smoke.py``, the examples and
the benchmarks), never when the library is imported.  If
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and no other
is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: one fixed
path (the path is part of what a run looks up, so a moving one never hits),
listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return its directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
