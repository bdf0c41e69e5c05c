"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compilation_cache` once, before their first
compile; importing this module changes nothing. If ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it and nothing is set here. Otherwise the cache lives at a
fixed path inside the checkout: the path is part of the cache key, so a
directory that moved between runs would never hit.

It also counts, from then on, what the process compiles: persistent-cache
hits and misses (a miss is a new entry written) and backend compiles (every
executable XLA builds or reads from the cache), through JAX's monitoring
events, which are process-wide, so the counts are too.
:func:`compile_snapshot` reads them; a compile between two snapshots taken
around steady-state work is a shape nobody warmed up."""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Dict

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_counts: Dict[str, float] = {"cache_hits": 0, "cache_misses": 0,
                             "backend_compiles": 0, "backend_compile_s": 0.0}
_lock = threading.Lock()
_listening = False


def _on_event(event: str, **_) -> None:
    key = _EVENTS.get(event)
    if key:
        with _lock:
            _counts[key] += 1


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        with _lock:
            _counts["backend_compiles"] += 1
            _counts["backend_compile_s"] += duration


def _count_compiles() -> None:
    """Install the monitoring listeners, once per process."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_snapshot() -> Dict[str, float]:
    """``{cache_hits, cache_misses, backend_compiles, backend_compile_s}``
    since :func:`use_compilation_cache` was first called."""
    with _lock:
        return dict(_counts)


def use_compilation_cache() -> Path:
    """Turn the persistent cache on and start counting compiles; returns the
    directory the cache uses."""
    _count_compiles()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return REPO_CACHE_DIR
