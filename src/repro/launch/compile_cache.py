"""JAX's persistent compilation cache at one fixed place per checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
module sets nothing. Otherwise the cache lives in ``<repo>/.jax_cache``: the
path is part of the cache key, so it carries no temporary names, process ids
or times, and a later run from the same checkout finds what an earlier one
compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
