"""JAX's persistent compilation cache, placed from outside.

The one place in the package that turns the cache on. Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax already honours it and
nothing is set here. Where it is not, the cache goes to a FIXED
directory inside the checkout (``<repo>/.jax_cache``, resolved from this
package's own path): the path is part of what a later process must find
again, so it is never a temp dir, a pid or a timestamp.

A process pinned to the CPU platform is left alone — the test suite
must not start depending on a warm cache.

Wherever the cache is on, its key covers the program's METADATA too
(``jax_compilation_cache_include_metadata_in_key``). jax leaves it out
by default, and an executable read back under such a key carries the
``op_name``s of whichever program was compiled first: two programs that
differ in a ``jax.named_scope`` alone share an entry, and the
instruction -> scope table a training loop states into a profiler
session (``observability/tracing.ProgramScopes``) would name the other
one's scopes. The price: an edit that moves a traced line compiles
again.
"""
from __future__ import annotations

import os
import threading

__all__ = ["configure", "cache_dir", "CacheCounter", "DEFAULT_DIR"]

#: <repo>/.jax_cache — listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure(config=None) -> str | None:
    """Point jax at the default directory unless a directory is already
    configured (``JAX_COMPILATION_CACHE_DIR`` or an earlier
    ``jax.config.update``) or the process is pinned to the CPU. Returns
    the directory in force, None when the cache stays off. Called once,
    from ``bigdl_tpu/__init__.py``; ``config`` stands in for
    ``jax.config`` in tests."""
    if config is None:
        import jax
        config = jax.config
    if (config.jax_compilation_cache_dir is None
            and config.jax_platforms != "cpu"):
        config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if config.jax_compilation_cache_dir is not None:
        config.update("jax_compilation_cache_include_metadata_in_key", True)
    return config.jax_compilation_cache_dir


def cache_dir() -> str | None:
    """The persistent compilation cache directory in force, or None."""
    import jax
    return jax.config.jax_compilation_cache_dir


class CacheCounter:
    """Counts this process's persistent-cache reads (``hits``) and
    writes after a compile (``misses``) from jax's own monitoring
    events. ``delta()`` returns the counts since the last call, so a
    caller can say per phase whether its programs were compiled or read
    back. Programs that compile in under
    ``jax_persistent_cache_min_compile_time_secs`` are never written
    and count as neither."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self._seen = (0, 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            with self._lock:
                self.hits += 1
        elif event == self._MISS:
            with self._lock:
                self.misses += 1

    def delta(self) -> dict:
        with self._lock:
            now = (self.hits, self.misses)
            out = {"read_back": now[0] - self._seen[0],
                   "compiled": now[1] - self._seen[1]}
            self._seen = now
        return out
