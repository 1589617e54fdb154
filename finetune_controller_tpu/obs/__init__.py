"""End-to-end observability: tracing, event timelines, step-phase profiling.

The answer to "where did this job's last 20 minutes go?" (docs/observability.md).
Three cooperating layers, zero dependencies beyond the stdlib:

* ``trace``  — trace ids minted at submit and threaded through every plane;
  OTel-compatible span dicts; the crash-safe trainer-side span log and the
  controller-side trace assembly (``GET /jobs/{id}/trace``);
* ``events`` — the structured lifecycle timeline appended to the job
  document (``GET /jobs/{id}/timeline``, ``ftc-ctl timeline``), plus the
  trainer-side ``events.jsonl`` that rides the artifact channel;
* ``prom``   — Prometheus *histogram* support for the ``/metrics`` exporter
  (step phases, queue wait, retry latency, serve TTFT) and the process-level
  ``ftc_build_info`` / ``ftc_uptime_seconds`` series;
* ``phase``  — the trainer's step-phase clock (input-wait / device-compute /
  checkpoint / sync), feeding the metrics CSV and the histograms.

The trainer-side pieces (``SpanRecorder``, ``EventLogWriter``, ``PhaseClock``)
are stdlib-only on purpose: they run inside pods that carry none of the
controller extras, exactly like ``resilience/heartbeat.py``.
"""

from .events import (
    EVENTS_FILENAME,
    EventLogWriter,
    make_event,
    parse_event_lines,
)
from .phase import PhaseClock
from .prom import Histogram, ObsHub
from .trace import (
    SpanRecorder,
    annotate,
    build_trace,
    new_span_id,
    new_trace_id,
    parse_span_lines,
    validate_trace,
)

__all__ = [
    "EVENTS_FILENAME",
    "EventLogWriter",
    "Histogram",
    "ObsHub",
    "PhaseClock",
    "SpanRecorder",
    "annotate",
    "build_trace",
    "make_event",
    "new_span_id",
    "new_trace_id",
    "parse_event_lines",
    "parse_span_lines",
    "validate_trace",
]
