"""Counts the programs JAX compiles (or loads from its cache) while armed.
Nothing may compile inside a measured window."""

from __future__ import annotations

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self) -> None:
        import jax.monitoring

        self.armed = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event == _EVENT:
            self.count += 1
            self.seconds += duration
