"""Where JAX keeps its persistent compilation cache.

A full-width serving run compiles the prefill and decode steps of every
layer; the persistent cache lets a second run on the same path load them
instead.  The cache key includes the directory, so the directory must not
move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no other directory;
* otherwise: ``<repo>/.jax_cache``, a fixed path in the checkout (listed
  in ``.gitignore``).

Entry points that compile (chip_smoke.py, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` before
their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
