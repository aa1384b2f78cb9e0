"""Where JAX keeps its persistent compilation cache.

Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first compile; importing the
library never touches the cache.  A ``JAX_COMPILATION_CACHE_DIR`` set in
the environment wins and JAX reads it itself; otherwise the cache lives at
a fixed ``.jax_cache/`` in the checkout, so every process run from the same
checkout finds the same entries.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
