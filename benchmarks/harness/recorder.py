"""Spans and series of one run, kept in memory.

A span is a named host-clock interval around a call into a layer; its
duration joins the series of the same name.  With tracing on, each span is
also a ``jax.profiler.TraceAnnotation``, so it lands in the profiler's trace
on the device's clock and idle gaps can be named by it.  Recording starts
when the measured window opens: set-up and the drain leave nothing here.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any


class Recorder:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.on = False
        self.series: dict[str, list[float]] = {}
        self._lock = threading.Lock()

    def add(self, name: str, value: float) -> None:
        if self.on:
            with self._lock:
                self.series.setdefault(name, []).append(value)

    @contextlib.contextmanager
    def span(self, name: str):
        ann: Any = contextlib.nullcontext()
        if self.annotate and self.on:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)
