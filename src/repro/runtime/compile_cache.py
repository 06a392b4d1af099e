"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first compile. Importing
``repro`` never does: tests keep the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (src/repro/runtime/ is three levels down): the
# directory is part of what a later process must find again, so it never
# carries a temporary name, a pid or a time
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and that directory stands; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
