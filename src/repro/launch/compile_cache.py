"""Persistent XLA compile cache for the command-line entry points.

Called at the top of ``main()`` of ``chip_smoke.py``, ``benchmarks/run.py``
and the ``repro.launch`` drivers — never at library import, and never by
the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed and inside the checkout (gitignored): a directory that moves from
# run to run never hits, so no temp, pid- or time-based path.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on before the first compile and
    return its directory.  A set ``JAX_COMPILATION_CACHE_DIR`` wins (JAX
    reads it itself, so nothing is set here); otherwise the cache goes to
    the checkout's ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
