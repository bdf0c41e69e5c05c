"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compilation_cache` once, before their first
compile; importing this module changes nothing. If ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it and nothing is set here. Otherwise the cache lives at a
fixed path inside the checkout: the path is part of the cache key, so a
directory that moved between runs would never hit."""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compilation_cache() -> Path:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return REPO_CACHE_DIR
