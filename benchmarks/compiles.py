"""Counts what jax compiles (or reads back from its persistent cache)
while the measured window is open. Any is a fault of the warm-up: the
run prints ``correct: false``."""
from __future__ import annotations

import threading

_BACKEND_COMPILE = "backend_compile"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Listens to jax's own monitoring events for the whole process;
    ``open()``/``close()`` bracket the window, ``in_window`` is the count
    of programs built (compiled, or deserialized from the cache) in it."""

    def __init__(self):
        import jax
        self._mu = threading.Lock()
        self.total = 0
        self.in_window = 0
        self._open = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _count(self) -> None:
        with self._mu:
            self.total += 1
            if self._open:
                self.in_window += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self._count()

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if _BACKEND_COMPILE in event:
            self._count()

    def open(self) -> None:
        with self._mu:
            self._open = True

    def close(self) -> None:
        with self._mu:
            self._open = False
