"""JAX's persistent compilation cache for the launchers.

Called from a launcher's ``main()`` (and ``chip_smoke.py``), never at
import: importing the package must not make the tests write a cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
    fixed path, so a later process of the same checkout finds it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
