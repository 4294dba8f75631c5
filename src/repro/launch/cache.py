"""JAX's persistent compilation cache, kept at one stable place.

The cache key includes the directory, so a path that moves between runs
never hits.  Entry points call ``enable_compile_cache()`` at start-up, not
at import: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself, and no other directory is set in code); otherwise the cache lives
in ``.jax_cache/`` at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (src/repro/launch -> repo root)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
