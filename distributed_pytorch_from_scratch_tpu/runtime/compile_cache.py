"""Where the persistent XLA compile cache lives.

Every entry point calls `enable_compile_cache()` before its first jit. The
cache directory is part of the cache key, so it must never move between
runs (no tempfile, pid or timestamp in it):

* `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself; this
  module sets no directory in code.
* unset: one fixed, git-ignored directory at the root of the checkout.

JAX's default skips programs that compile in under a second, which is most
of the serving programs (one per prefill bucket and decode shape); the
threshold is dropped to zero so those are cached too.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_counts = {"hits": 0, "misses": 0}
_listening = False


def _count(event: str, **_) -> None:
    if event == _HIT:
        _counts["hits"] += 1
    elif event == _MISS:
        _counts["misses"] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    global _listening
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_count)
    return path


def compile_cache_stats() -> dict:
    """{'dir', 'hits', 'misses'} for this process so far: a hit is a program
    loaded from the directory, a miss one compiled and written to it."""
    return {"dir": os.environ.get(ENV_VAR) or DEFAULT_DIR, **_counts}
