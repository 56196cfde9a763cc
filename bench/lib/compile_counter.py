"""Counts the XLA programs a process builds, from ``jax.monitoring``."""
from __future__ import annotations

import collections

import jax


class CompileCounter:
    """Counts XLA executables built while it is entered: every backend
    compile request, how many of them the persistent compilation cache
    answered, and the requests per jitted function's name."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names = collections.Counter()

    @property
    def compiled(self) -> int:
        """Programs the backend compiled, not loaded from the cache."""
        return self.programs - self.cache_hits

    def _on_duration(self, event, duration, fun_name="?", **_):
        if event == self._COMPILE:
            self.programs += 1
            self.seconds += duration
            self.names[fun_name] += 1

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
