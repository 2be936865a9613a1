"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and stands:
nothing here overrides it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The directory is
part of the cache's key, so a path that moves between runs (a temporary
name, a pid, a time) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    the directory.  Call before the first compile."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
