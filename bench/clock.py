"""Counts the backend compiles of a process and their seconds, from JAX's
own monitoring events (a persistent-cache hit compiles nothing). One
listener is registered per process; it stays for the process's life."""
from __future__ import annotations

import jax

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _EVENT:
            self.count += 1
            self.seconds += duration
