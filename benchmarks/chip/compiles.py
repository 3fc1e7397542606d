"""Compiles and persistent-cache loads, from JAX's own monitoring events.

Copied from the program's ``chip_smoke.py`` listener and extended with
counts.  JAX reports ``backend_compile_duration`` for every program it
builds, whether the backend compiled it or it was read from the persistent
cache; a ``cache_hits`` event just before marks the second kind.  So a run
can tell real compiles from cache loads inside its window.  Create one per
process: JAX keeps its listeners for the life of the process.
"""
from __future__ import annotations

import jax


class Compiles:
    def __init__(self):
        self.compiled, self.compile_s = 0, 0.0
        self.loaded, self.load_s = 0, 0.0
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if self._hit:
            self.loaded += 1
            self.load_s += duration
        else:
            self.compiled += 1
            self.compile_s += duration
        self._hit = False

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def snapshot(self) -> tuple:
        """(compiled, compile seconds, loaded, load seconds) so far."""
        return self.compiled, self.compile_s, self.loaded, self.load_s
