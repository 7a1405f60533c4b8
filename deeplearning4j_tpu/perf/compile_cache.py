"""Persisted XLA compilation cache for serving cold starts.

The warmed TuningRecord bucket ladder (PR 13) removes serve-time
compiles but a fresh process still pays every warmup compile from
scratch. Pointing JAX's persistent compilation cache at a directory
makes the SECOND cold start replay executables from disk instead of
re-running XLA — the fleet's instant-start story gets a second lever
beyond lease-gated warmup.

``enable_compilation_cache(dir)`` is process-global and idempotent; the
thresholds are dropped to zero so even the small CPU-test programs cache
(the default config skips sub-second compiles, which on TPU is fine but
would make the cold-start test meaningless). Cache *hits* are observable
via :func:`cache_hits`, read from the process's one ``jax.monitoring``
listener (``perf/compile_watch.py``) — that is what the cold-start test
asserts on.

Where the cache lives is decided HERE and nowhere else, so it can be
placed from outside: ``JAX_COMPILATION_CACHE_DIR``, when set, is the
directory (JAX reads it itself; an explicit different argument is logged
and ignored); unset, the explicit argument; with neither, the fixed
``<checkout>/.jax_cache``. The path is part of the cache key's
environment, so it is never derived from a temp dir, a pid or the time.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from deeplearning4j_tpu.perf.compile_watch import cache_hits, install_listener

log = logging.getLogger(__name__)

__all__ = ["enable_compilation_cache", "cache_hits", "cache_dir",
           "resolve_cache_dir", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache — fixed, gitignored
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_dir: Optional[str] = None


def resolve_cache_dir(directory=None) -> str:
    """The one placement rule: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``directory``, else :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        if directory is not None and str(directory) != env:
            log.warning("JAX_COMPILATION_CACHE_DIR=%s is set; ignoring "
                        "compile cache directory %s", env, directory)
        return env
    return str(directory) if directory is not None else DEFAULT_CACHE_DIR


def enable_compilation_cache(directory=None, *,
                             min_compile_time_secs: float = 0.0) -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`resolve_cache_dir` ``(directory)`` (created on first write).
    Process-global; calling again with the same directory is a no-op,
    with a different one re-points the cache and logs. Returns the
    directory in use."""
    global _dir
    import jax

    directory = resolve_cache_dir(directory)
    with _lock:
        if _dir == directory:
            return directory
        if _dir is not None:
            log.warning("compilation cache re-pointed: %s -> %s",
                        _dir, directory)
    if jax.config.jax_compilation_cache_dir != directory:
        # (with the env var set JAX already points there: nothing to set)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        _dir = directory
    install_listener()
    log.info("persistent compilation cache enabled at %s", directory)
    return directory


def cache_dir() -> Optional[str]:
    with _lock:
        return _dir
