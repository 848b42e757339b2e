"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``launch/train.py``, the examples) call
:func:`enable_compile_cache` before their first compile, so that a second
run of the same checkout finds what the first one compiled. Importing this
module touches no JAX state.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored). The path is part of what a run looks
# up, so it must not move between runs: no temp name, pid or timestamp.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache lives at :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
