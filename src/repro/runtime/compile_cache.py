"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the chip
(``chip_smoke.py``, ``benchmarks/run.py``, ``benchmarks/kernel_bench.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing, so the caller's directory wins.
* otherwise: the fixed ``<repo>/.jax_cache`` (ignored by git).  The path is
  part of each entry's key, so it never depends on a temp directory, a pid
  or the time.
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
