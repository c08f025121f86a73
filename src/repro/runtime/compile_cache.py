"""Where JAX's persistent compilation cache lives for this repo's entry
points (``chip_smoke.py``, ``benchmarks/run.py``).

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins.  Otherwise the cache goes to ``.jax_cache/`` at the repository
root.  The path is fixed (resolved from this file, never a temp name,
pid or time) so that a later run in the same checkout finds the
executables an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call once, before the process's first
    compile: JAX settles whether the cache is in use at that compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
