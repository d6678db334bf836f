"""One place that decides where JAX keeps its persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in code
(JAX reads the variable itself). Otherwise the cache lives at the fixed
path ``<repo>/.jax_cache``: the directory is part of the cache key, so it
never depends on a temporary name, a process id or the time.

The key includes each instruction's metadata (its ``op_name``, where the
step's named scopes live). JAX leaves metadata out of the key by default,
so the same program under other scopes would load an executable whose HLO
names other layers, or none.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
