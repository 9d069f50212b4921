"""JAX's persistent compilation cache, for the programs a user runs.

The entry points (chip_smoke.py, launch/serve.py, benchmarks/run.py and the
examples) call `enable()` once before their first compile, so a second run
of the same program loads its executables instead of compiling them again.
The library and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory at the root of the checkout: the cache is only found
# again by a run that looks in the same place
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to `CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
