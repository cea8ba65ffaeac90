"""JAX's persistent compilation cache, placed from outside the program.

``enable()`` is called by the programs that compile a real model
(``launch/serve.py``, ``chip_smoke.py``), never at import time and never by
the tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this code sets no other directory.  Otherwise the cache lives at a fixed
path inside the checkout: the path is part of the cache key, so a directory
that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
