"""Where the entry points keep JAX's persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing here overrides it; otherwise the cache lives
at ``.jax_cache`` in the root of the checkout.  The path is part of a
cache entry's identity, so it is fixed — never a temp name, a pid or a
time — and a second run of the same checkout finds the first run's code.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
