"""Counts XLA backend compiles and their seconds, from JAX's own
monitoring events.  The event fires for every executable a process
loads, whether compiled or read from the persistent cache."""
from __future__ import annotations

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == EVENT:
            self.n += 1
            self.s += duration

    def snapshot(self):
        return self.n, self.s
