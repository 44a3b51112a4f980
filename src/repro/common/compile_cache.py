"""Where JAX keeps its persistent compilation cache.

A run on the chip starts with no compiled code; the cache lets a second
process (or a later run on the same disk) skip the UNet forward compiles.
JAX keys cache entries by the directory, so the path must not move
between runs: it is never made from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — src/repro/common/ is three levels below the root.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing. Otherwise the cache goes to ``DEFAULT_DIR`` inside the
    checkout. Call it at the start of an entry point's ``main()``, never at
    import or from tests.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
