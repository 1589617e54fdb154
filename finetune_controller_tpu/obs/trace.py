"""Job-lifecycle tracing: trace ids, OTel-compatible spans, trace assembly.

A ``trace_id`` is minted once at submit (``task_builder``) and rides the job
document, the backend dispatch env (``FTC_TRACE_ID``), supervisor
resubmissions, and serve loads — every attempt and every plane stamps the
same id, so one id names the job's whole life.

Spans are plain dicts in OTel shape (name, trace/span/parent ids, start/end
nanoseconds, attributes) so they can be shipped to any OTLP-speaking backend
without translation.  Two sources:

* the **trainer** records spans crash-safe to ``trace/trainer.jsonl`` in its
  artifacts dir (one flushed line per finished span — ``SpanRecorder``); the
  artifact sidecar ships them;
* the **controller** derives its spans from the job's event timeline
  (``build_trace``): the timeline is already recorded crash-safe in the job
  document, so the controller's span tree needs no second persistence path —
  pending/attempt/backoff/promotion/serve phases are reconstructed from the
  events they bracket, which also makes the tree gap-free by construction
  (every lifecycle event falls inside the phase span it delimits).

``GET /jobs/{id}/trace`` assembles both sources; the monitor exports the
same assembly to ``{artifacts_uri}/trace/trace.json`` when a job reaches a
terminal state, so traces survive control-plane restarts.

A third clock is the PROFILER's: :func:`annotate` puts a host span into the
open ``jax.profiler`` session, beside the device's operations, and into
nothing else — that session's file is its only store
(docs/observability.md §Names in a profile).

The minute BEFORE ``fit`` is the start-up log's (:class:`StartupLog`, one
for the process: ``STARTUP``): imports, the backend's start, ``Trainer()``,
every program compiled or loaded, the first step — kept in memory in the
same span shape until the first ``Trainer.step`` returns, and written to
disk by the ``SpanRecorder`` that adopts it (docs/observability.md §Start-up).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
import uuid
import weakref
from typing import Any

logger = logging.getLogger(__name__)

TRACE_DIRNAME = "trace"
TRAINER_SPANS_FILENAME = "trainer.jsonl"

#: nesting tolerance when validating child ⊆ parent intervals — events and
#: spans share one host clock, but float epoch→ns round-trips deserve slack
_EPS_NS = int(1e6)  # 1 ms


def new_trace_id() -> str:
    """128-bit lowercase hex trace id (the OTel wire width)."""
    return uuid.uuid4().hex


def new_span_id() -> str:
    """64-bit lowercase hex span id."""
    return uuid.uuid4().hex[:16]


def annotate(name: str, **attrs: Any):
    """A host span on the profiler's clock: ``with annotate("prefetch.take",
    depth=2): ...``.  Recorded only while a ``jax.profiler`` session is open
    (the trainer's ``profile_steps`` window, ``ftc-ctl profile JOB``, a
    benchmark's traced run); outside one it costs a flag test.  A span that
    carries ``step_num`` is the profiler's step marker
    (``StepTraceAnnotation``).  JAX is imported here, not by this module:
    the API server and pods without JAX import ``obs`` and get a null
    context."""
    try:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
    except ImportError:
        return contextlib.nullcontext()
    cls = StepTraceAnnotation if "step_num" in attrs else TraceAnnotation
    return cls(name, **attrs)


def make_span(
    name: str,
    trace_id: str,
    *,
    start_ns: int,
    end_ns: int | None = None,
    parent_span_id: str | None = None,
    span_id: str | None = None,
    status: str = "ok",
    **attrs: Any,
) -> dict[str, Any]:
    return {
        "name": name,
        "trace_id": trace_id,
        "span_id": span_id or new_span_id(),
        "parent_span_id": parent_span_id,
        "start_ns": int(start_ns),
        "end_ns": None if end_ns is None else int(end_ns),
        "status": status,
        "attributes": {k: v for k, v in attrs.items() if v is not None},
    }


class SpanRecorder:
    """Trainer-side span log: one flushed JSONL line per FINISHED span
    (crash-safe — a kill mid-run loses at most the spans still open).

    Stdlib-only (runs inside pods).  Thread-safe: the async-checkpoint
    thread and the fit loop may both finish spans.

    Given the process's ``startup`` log it ADOPTS it: the spans the log has
    finished are written here at once, those it finishes later as they
    finish (the first step's, the root's when the log closes; after that a
    late ``compile`` or a second trainer's ``trainer.build``), each with
    this recorder's trace id, service and attempt, and hung — where it has
    no parent — under the first parentless span this recorder starts
    (``fit``), which then starts where the log's root did.  A log that had
    closed before (a process that stepped without a recorder) is not
    adopted: its spans are another job's minute.
    """

    def __init__(
        self,
        artifacts_dir: str,
        trace_id: str,
        *,
        service: str = "trainer",
        attempt: int = 0,
        enabled: bool = True,
        startup: "StartupLog | None" = None,
        _clock_ns=time.time_ns,
    ):
        self.dir = os.path.join(artifacts_dir, TRACE_DIRNAME)
        self.path = os.path.join(self.dir, TRAINER_SPANS_FILENAME)
        self.trace_id = trace_id
        self.service = service
        self.attempt = attempt
        self.enabled = enabled and bool(trace_id)
        self._clock_ns = _clock_ns
        self._lock = threading.Lock()
        #: span_id -> the open profiler annotation of a started span
        self._open: dict[str, Any] = {}
        self.write_failures = 0
        #: the first parentless span started: what adopted spans hang under
        self._root: dict[str, Any] | None = None
        #: where the adopted log's root starts (None: nothing adopted)
        self._since_ns = startup.adopt(self) if startup is not None else None

    def start(self, name: str, *, parent: dict | None = None,
              **attrs: Any) -> dict[str, Any]:
        span = make_span(
            name, self.trace_id,
            start_ns=self._clock_ns(),
            parent_span_id=parent["span_id"] if parent else None,
            service=self.service, attempt=self.attempt or None, **attrs,
        )
        if parent is None and self._root is None:
            self._root = span
            if self._since_ns is not None:
                span["start_ns"] = min(span["start_ns"], self._since_ns)
        # the same span on the profiler's clock (whether or not the JSONL log
        # is enabled: a profile window is armed independently of FTC_TRACE)
        ann = annotate(name)
        ann.__enter__()
        with self._lock:
            self._open[span["span_id"]] = ann
        return span

    def finish(self, span: dict[str, Any], *, status: str = "ok",
               **attrs: Any) -> None:
        span["end_ns"] = self._clock_ns()
        span["status"] = status
        if attrs:
            span["attributes"].update(
                {k: v for k, v in attrs.items() if v is not None}
            )
        with self._lock:
            ann = self._open.pop(span["span_id"], None)
        if ann is not None:
            ann.__exit__(None, None, None)
        self._write(span)

    def record(self, name: str, *, start_ns: int, end_ns: int,
               status: str = "ok", **attrs: Any) -> dict[str, Any]:
        """Append an already-timed span (e.g. one a rollout worker stamped
        with its own ``time.time_ns`` and shipped over the transport) — same
        crash-safe JSONL write as :meth:`finish`, but the interval is the
        caller's, not this recorder's clock."""
        span = make_span(
            name, self.trace_id,
            start_ns=int(start_ns), end_ns=int(end_ns), status=status,
            service=self.service, attempt=self.attempt or None, **attrs,
        )
        self._write(span)
        return span

    def adopt(self, span: dict[str, Any]) -> None:
        """Write a span the start-up log finished as one of this recorder's:
        its ids and interval kept, the trace id, service and attempt filled
        in, a parentless one hung under this recorder's first span."""
        parent = span["parent_span_id"]
        if parent is None and self._root is not None:
            parent = self._root["span_id"]
        attrs = {"service": self.service, **span["attributes"]}
        if self.attempt:
            attrs["attempt"] = self.attempt
        self._write({**span, "trace_id": self.trace_id,
                     "parent_span_id": parent, "attributes": attrs})

    def _write(self, span: dict[str, Any]) -> None:
        if not self.enabled:
            return
        try:
            with self._lock:
                os.makedirs(self.dir, exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(json.dumps(span) + "\n")
                    f.flush()
        except OSError:
            with self._lock:  # writers race across threads
                self.write_failures += 1
                failures = self.write_failures
            level = logging.WARNING if failures == 1 else logging.DEBUG
            logger.log(level, "span write to %s failed (%d so far)",
                       self.path, failures, exc_info=True)

    class _SpanCtx:
        def __init__(self, recorder: "SpanRecorder", span: dict):
            self.recorder, self.span = recorder, span

        def __enter__(self):
            return self.span

        def __exit__(self, exc_type, exc, tb):
            self.recorder.finish(
                self.span, status="error" if exc_type else "ok"
            )
            return False

    def span(self, name: str, *, parent: dict | None = None, **attrs: Any):
        """``with recorder.span("checkpoint", step=40): ...``"""
        return self._SpanCtx(self, self.start(name, parent=parent, **attrs))


# ---------------------------------------------------------------------------
# The start-up log
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = "/jax/compilation_cache/"


def _process_start_ns() -> int | None:
    """When this process started, on ``time.time_ns``'s clock: its start time
    in ``/proc/self/stat`` (ticks since boot) against ``CLOCK_BOOTTIME``.
    None where either cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name may hold blanks: count from its closing ")"
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_s = (time.clock_gettime(time.CLOCK_BOOTTIME)
                 - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.time_ns() - int(age_s * 1e9) if age_s >= 0 else None


class _TimedLoader:
    """Stands for a module's real loader while the module is made: times
    ``create_module`` (an extension's ``dlopen``) and ``exec_module`` (the
    module's body) on the log's import clock, and hands the module back to
    the real loader before the body runs, so nothing keeps this object."""

    def __init__(self, loader: Any, log: "StartupLog"):
        self._loader, self._log = loader, log

    def __getattr__(self, name: str) -> Any:
        return getattr(self._loader, name)

    def create_module(self, spec: Any) -> Any:
        create = getattr(self._loader, "create_module", None)
        if create is None:
            return None
        frame = self._log._import_enter(spec.name)
        try:
            return create(spec)
        finally:
            self._log._import_exit(frame)

    def exec_module(self, module: Any) -> None:
        spec = getattr(module, "__spec__", None)
        if spec is not None and spec.loader is self:
            spec.loader = self._loader
        if getattr(module, "__loader__", None) is self:
            module.__loader__ = self._loader
        frame = self._log._import_enter(module.__name__)
        try:
            self._loader.exec_module(module)
        finally:
            self._log._import_exit(frame)
        if module.__name__ == "jax":
            self._log._listen()


class _ImportObserver:
    """A meta-path finder that finds nothing itself: it asks the finders
    behind it, times their search, and wraps the loader they name."""

    def __init__(self, log: "StartupLog"):
        self._log = log

    def find_spec(self, name: str, path: Any = None, target: Any = None) -> Any:
        frame = self._log._import_enter(name)
        try:
            spec = None
            finders = list(sys.meta_path)
            # those BEHIND it only: two logs' observers (a test's before the
            # process's) then ask each other once, not for ever
            with contextlib.suppress(ValueError):
                finders = finders[finders.index(self) + 1:]
            for finder in finders:
                find = getattr(finder, "find_spec", None)
                spec = find(name, path, target) if find is not None else None
                if spec is not None:
                    break
        finally:
            self._log._import_exit(frame)
        if spec is not None and hasattr(spec.loader, "exec_module"):
            spec.loader = _TimedLoader(spec.loader, self._log)
        return spec


class StartupLog:
    """What the process did before its first training step: one tree of spans
    under a root ``startup``, kept in memory in :func:`make_span`'s shape.

    * **imports** are a counter, not a span (the package imports lazily, all
      through ``Trainer()`` and the first trace): while the log is open an
      observer on ``sys.meta_path`` times every module found, created and
      executed, EXCLUSIVE of the imports it triggers, by top-level package —
      the root carries ``import_s`` and ``import_by_package``, every other
      span the ``import_s`` that fell inside it;
    * ``span(name)`` is a piece of the program's own start-up
      (``startup.backend``, ``trainer.build`` and its children,
      ``trainer.first_step``), on the profiler's clock too;
    * ``compile`` is one program compiled or loaded, put together from what
      ``jax.monitoring`` says of it: ``fun_name``, ``trace_s``, ``lower_s``,
      ``backend_s``, ``cache`` (``hit`` | ``miss`` | ``off``), ``cache_load_s``
      on a hit, ``cache_written`` where a miss was stored, ``step`` for a
      trainer's step program.  Programs under ``SMALL_COMPILE_S`` are folded
      into one ``compile.small`` with their count.

    ``close()`` — the first ``Trainer.step`` to return calls it, or a serve
    load — ends the root and takes the observer off the import machinery.
    The compile listener stays (it is called when something traces or
    compiles, never on a cached step's path): a later ``compile`` goes to the
    recorder that adopted the log (``SpanRecorder(startup=...)``), if one is
    alive, and ``programs`` keeps each program's last compile by name for
    ``analysis/recompile_guard.py`` to quote.

    The process has one, ``STARTUP``, opened by the package's ``__init__``;
    code reaches it as ``trace.STARTUP`` so that a test can put its own there.
    """

    SMALL_COMPILE_S = 0.010

    def __init__(self, *, from_process_start: bool = True,
                 _clock_ns=time.time_ns):
        self._clock_ns = _clock_ns
        start = _process_start_ns() if from_process_start else None
        self.root = make_span(
            "startup", "", start_ns=_clock_ns() if start is None else start,
            anchor="package_import" if start is None else "process")
        #: tested by ``Trainer.step`` on every call
        self.closed = False
        #: the finished spans, the root last; frozen once closed
        self.spans: list[dict[str, Any]] = []
        #: ``fun_name`` -> its last compile's attributes and ``count``
        self.programs: dict[str, dict[str, Any]] = {}
        self._step_programs: set[str] = set()
        self._lock = threading.RLock()
        self._local = threading.local()
        self._import_s = 0.0
        self._by_package: dict[str, float] = {}
        self._compile_s = 0.0
        self._small: dict[str, Any] | None = None
        self._observer = _ImportObserver(self)
        self._listening = False
        self._sink: weakref.ref | None = None

    # ---- opening and closing --------------------------------------------------

    def open(self) -> "StartupLog":
        """Put the observer first on ``sys.meta_path``; hear JAX's compile
        events from the moment ``jax`` is imported (now, if it is)."""
        sys.meta_path.insert(0, self._observer)
        if sys.modules.get("jax") is not None:
            self._listen()
        return self

    def close(self) -> None:
        with self._lock:
            if self.closed:
                return
            self.closed = True
            self._unobserve()
            if self._small is not None:
                self._finished(self._small)
            self.root["end_ns"] = self._clock_ns()
            self.root["attributes"].update(
                import_s=round(self._import_s, 6),
                import_by_package=self.import_by_package(),
                compile_s=round(self._compile_s, 6))
            self._finished(self.root)

    def shutdown(self) -> None:
        """Take the observer and the listeners away (a test's own log)."""
        self._unobserve()
        with self._lock:
            was, self._listening = self._listening, False
        if was:
            import jax.monitoring as monitoring

            monitoring.unregister_scalar_listener(self._on_scalar)
            monitoring.unregister_event_duration_listener(self._on_duration)
            monitoring.unregister_event_listener(self._on_event)

    def _unobserve(self) -> None:
        with contextlib.suppress(ValueError):
            sys.meta_path.remove(self._observer)

    def _listen(self) -> None:
        import jax.monitoring as monitoring

        with self._lock:
            was, self._listening = self._listening, True
        if not was:
            monitoring.register_scalar_listener(self._on_scalar)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)

    # ---- where finished spans go ---------------------------------------------

    def adopt(self, recorder: "SpanRecorder") -> int | None:
        """Make ``recorder`` the one that writes this log's spans.  An open
        log hands over what it has finished and returns its root's start; a
        closed one hands over nothing, and sends only what it hears later."""
        with self._lock:
            self._sink = weakref.ref(recorder)
            if self.closed:
                return None
            for span in self.spans:
                recorder.adopt(span)
            return self.root["start_ns"]

    def _finished(self, span: dict[str, Any]) -> None:
        """Keep a finished span (while the log is open; the root and the
        folded programs come last) and hand it to the adopting recorder."""
        with self._lock:
            if not self.closed or span is self.root or span is self._small:
                self.spans.append(span)
            sink = self._sink() if self._sink is not None else None
            if sink is not None:
                sink.adopt(span)

    def _fold(self, span: dict[str, Any]) -> None:
        """A program too small for a span of its own, into ``compile.small``
        (while the log is open; afterwards it is counted nowhere)."""
        with self._lock:
            if self.closed:
                return
            if self._small is None:
                self._small = make_span(
                    "compile.small", "", start_ns=span["start_ns"],
                    parent_span_id=self.root["span_id"], step=False, count=0,
                    trace_s=0.0, lower_s=0.0, backend_s=0.0, import_s=0.0)
            total, part = self._small["attributes"], span["attributes"]
            total["count"] += 1
            for key in ("trace_s", "lower_s", "backend_s", "import_s"):
                total[key] = round(total[key] + part[key], 6)
            self._small["end_ns"] = span["end_ns"]

    # ---- the program's own spans ---------------------------------------------

    def _stack(self) -> list[dict[str, Any]]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _parent(self) -> tuple[bool, dict[str, Any] | None]:
        """Whether a span that starts now has anywhere to go, and what it
        hangs under: the innermost open span of this thread, else the root
        while the log is open, else nothing (the recorder's own first span)."""
        stack = self._stack()
        with self._lock:
            live = not self.closed or (
                self._sink is not None and self._sink() is not None)
            parent = stack[-1] if stack else None if self.closed else self.root
            return live, parent

    def _counters(self) -> tuple[float, float]:
        """The import and compile seconds counted so far."""
        with self._lock:
            return self._import_s, self._compile_s

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """``with log.span("trainer.build", mode="lora") as span: ...`` — the
        span's dict, to add attributes to.  After the log has closed the span
        goes to the adopting recorder alone, and without one nowhere."""
        live, parent = self._parent()
        if not live:
            yield make_span(name, "", start_ns=0)
            return
        span = make_span(
            name, "", start_ns=self._clock_ns(),
            parent_span_id=parent["span_id"] if parent else None, **attrs)
        import_s, compile_s = self._counters()
        stack = self._stack()
        stack.append(span)
        try:
            # on the profiler's clock too — unless that would import JAX
            with annotate(name) if sys.modules.get("jax") is not None \
                    else contextlib.nullcontext():
                yield span
        except BaseException:
            span["status"] = "error"
            raise
        finally:
            stack.pop()
            span["end_ns"] = self._clock_ns()
            import_end, compile_end = self._counters()
            span["attributes"].update(
                import_s=round(import_end - import_s, 6),
                compile_s=round(compile_end - compile_s, 6))
            self._finished(span)

    def seconds(self, name: str) -> float | None:
        """The summed seconds of the finished spans called ``name``."""
        with self._lock:
            found = [s for s in self.spans if s["name"] == name]
        if not found:
            return None
        return sum(s["end_ns"] - s["start_ns"] for s in found) / 1e9

    def summary(self) -> dict[str, float]:
        """``startup_s`` of the ``train-started`` event: the seconds of
        imports so far, of the backend's start and of ``Trainer()``."""
        out = {"import": round(self._counters()[0], 3)}
        for key, name in (("backend", "startup.backend"),
                          ("trainer_build", "trainer.build")):
            seconds = self.seconds(name)
            if seconds is not None:
                out[key] = round(seconds, 3)
        return out

    # ---- imports ---------------------------------------------------------------

    def _import_enter(self, module: str) -> list:
        try:
            frames = self._local.imports
        except AttributeError:
            frames = self._local.imports = []
        # [package, started, seconds of the imports this one triggered]
        frame = [module.partition(".")[0], time.perf_counter(), 0.0]
        frames.append(frame)
        return frame

    def _import_exit(self, frame: list) -> None:
        seconds = time.perf_counter() - frame[1]
        frames = self._local.imports
        frames.pop()
        if frames:
            frames[-1][2] += seconds
        own = seconds - frame[2]
        with self._lock:
            self._import_s += own
            self._by_package[frame[0]] = self._by_package.get(frame[0], 0.0) + own

    def import_by_package(self) -> dict[str, float]:
        """Exclusive import seconds by top-level package, largest first:
        every package over 50 ms, and as many more as put nine tenths of
        ``import_s`` on a name; the rest under ``(other)``."""
        with self._lock:
            total = self._import_s
            ranked = sorted(self._by_package.items(), key=lambda kv: -kv[1])
        out, named = {}, 0.0
        for package, seconds in ranked:
            if seconds < 0.05 and named >= 0.9 * total:
                break
            out[package] = round(seconds, 6)
            named += seconds
        if len(out) < len(ranked):
            out["(other)"] = round(total - named, 6)
        return out

    # ---- programs compiled or loaded ----------------------------------------

    def step_program(self, python_name: str) -> None:
        """``jax.jit`` of the function called ``python_name`` is one of a
        trainer's step programs: its compiles carry ``step: true``."""
        with self._lock:
            self._step_programs.add(f"jit({python_name})")

    def step_compiles(self) -> list[dict[str, Any]]:
        """Each step program's last compile, with how often it compiled."""
        with self._lock:
            return [p for p in self.programs.values() if p["step"]]

    def _pending(self) -> dict[str, Any]:
        try:
            return self._local.pending
        except AttributeError:
            # tracing: ``import_s`` as each open trace began; traced: the
            # finished outermost traces no program has claimed, by function
            self._local.pending = {"tracing": [], "traced": {}}
            return self._local.pending

    def _on_scalar(self, event: str, value: float, **kw: Any) -> None:
        if event == _TRACE_EVENT:   # a trace begins (``log_elapsed_time``)
            self._pending()["tracing"].append(self._counters()[0])

    def _on_duration(self, event: str, seconds: float, **kw: Any) -> None:
        if event == _TRACE_EVENT:
            p, now = self._pending(), self._counters()[0]
            import_s = now - (p["tracing"].pop() if p["tracing"] else now)
            if not p["tracing"]:    # outermost: the traces inside it are its
                p["traced"][kw.get("fun_name", "")] = (seconds, import_s)
        elif event == _LOWER_EVENT:
            p = self._pending()
            module = kw.get("fun_name", "")
            # ``jit(f)`` lowers what the trace of ``f`` gave; a trace nothing
            # lowered (``jax.eval_shape``; one a lowering rule made) goes
            p["trace"] = p["traced"].get(
                module[module.find("(") + 1:module.rfind(")")])
            p["traced"].clear()
            p["lower_s"] = seconds
        elif event == _BACKEND_EVENT:
            self._compiled(kw.get("fun_name", ""), seconds, self._pending())
        elif event == _CACHE_EVENTS + "cache_retrieval_time_sec":
            self._pending()["cache_load_s"] = round(seconds, 6)

    def _on_event(self, event: str, **kw: Any) -> None:
        if not event.startswith(_CACHE_EVENTS):
            return
        what = event[len(_CACHE_EVENTS):]
        if what == "compile_requests_use_cache":
            self._pending().setdefault("cache", "miss")
        elif what == "cache_hits":
            self._pending()["cache"] = "hit"
        elif what == "cache_misses":    # recorded where the entry is written
            self._pending()["cache_written"] = True

    def _compiled(self, fun_name: str, backend_s: float, p: dict) -> None:
        trace_s, import_s = p.pop("trace", None) or (0.0, 0.0)
        lower_s = p.pop("lower_s", 0.0)
        seconds = trace_s + lower_s + backend_s
        _, parent = self._parent()
        with self._lock:
            step = fun_name in self._step_programs
        # its parts need not touch (a program lowered in one span, compiled
        # in the next): the span ends where the backend did and is as long
        # as its parts, but never starts before the span it lies in
        end_ns = self._clock_ns()
        span = make_span(
            "compile", "",
            start_ns=max(end_ns - int(seconds * 1e9),
                         parent["start_ns"] if parent else 0),
            end_ns=end_ns, parent_span_id=parent["span_id"] if parent else None,
            fun_name=fun_name, step=step, trace_s=round(trace_s, 6),
            lower_s=round(lower_s, 6), backend_s=round(backend_s, 6),
            cache=p.pop("cache", "off"), cache_load_s=p.pop("cache_load_s", None),
            cache_written=p.pop("cache_written", None),
            import_s=round(import_s, 6))
        with self._lock:
            self._compile_s += max(seconds - import_s, 0.0)
            count = self.programs.get(fun_name, {}).get("count", 0) + 1
            self.programs[fun_name] = {**span["attributes"], "count": count}
        if seconds < self.SMALL_COMPILE_S and not step:
            self._fold(span)
        else:
            self._finished(span)


#: the process's start-up log (``finetune_controller_tpu/__init__.py`` opens it)
STARTUP = StartupLog()


def parse_span_lines(raw: bytes | str) -> list[dict[str, Any]]:
    """Decode a span JSONL payload; torn lines are skipped."""
    if isinstance(raw, bytes):
        raw = raw.decode(errors="replace")
    out: list[dict[str, Any]] = []
    for line in raw.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "span_id" in doc and "start_ns" in doc:
            out.append(doc)
    return out


# ---------------------------------------------------------------------------
# Controller-side trace assembly
# ---------------------------------------------------------------------------

#: events that end the "pending" phase — it runs submit → execution (the
#: "admitted" instant stays INSIDE it so admitted→running is never a gap)
_PENDING_ENDERS = {"running", "failed", "cancelled", "succeeded"}
#: events that end an attempt span (the job left execution)
_ATTEMPT_ENDERS = {
    "retrying", "failed", "succeeded", "cancelled", "lost", "lease-killed",
}


def _ns(ts: float) -> int:
    return int(float(ts) * 1e9)


def build_trace(
    job: dict[str, Any],
    trainer_spans: list[dict[str, Any]] | None = None,
    *,
    now: float | None = None,
) -> dict[str, Any]:
    """Assemble the job's span tree from its event timeline + trainer spans.

    ``job`` is the job document (``JobRecord.model_dump()``): ``events``,
    ``metadata.trace_id``, ``submitted_at``, ``end_time``.  Returns
    ``{"trace_id", "job_id", "spans": [...], "problems": [...]}`` where
    ``problems`` is ``validate_trace``'s verdict (empty = well-formed,
    gap-free).  Phases still open when assembled are closed at ``now`` and
    marked ``in_progress``.
    """
    now = time.time() if now is None else now
    events = sorted(
        (e for e in (job.get("events") or []) if isinstance(e.get("ts"), (int, float))),
        key=lambda e: e["ts"],
    )
    trace_id = (job.get("metadata") or {}).get("trace_id") or ""
    first_ts = events[0]["ts"] if events else job.get("submitted_at") or now
    start_ts = min(first_ts, job.get("submitted_at") or first_ts)
    last_ts = events[-1]["ts"] if events else start_ts
    end_ts = job.get("end_time") or None
    root_open = end_ts is None and (job.get("status") or "") not in (
        "succeeded", "failed", "cancelled",
    )
    root_end = max(filter(None, (end_ts, last_ts, now if root_open else None)))
    root = make_span(
        "job", trace_id,
        start_ns=_ns(start_ts), end_ns=_ns(root_end),
        service="controller", job_id=job.get("job_id"),
        status_final=job.get("status"), in_progress=root_open or None,
    )
    spans: list[dict[str, Any]] = [root]

    def phase(name: str, start: float, end: float | None, **attrs):
        open_ = end is None
        spans.append(make_span(
            name, trace_id,
            start_ns=_ns(start), end_ns=_ns(root_end if open_ else end),
            parent_span_id=root["span_id"], service="controller",
            in_progress=open_ or None, **attrs,
        ))
        return spans[-1]

    pending_since: float | None = None
    attempt_since: float | None = None
    attempt_no = 0
    promo_since: float | None = None
    serve_since: float | None = None
    for e in events:
        name, ts, attrs = e["event"], e["ts"], e.get("attrs") or {}
        if name in ("submitted", "resubmitted", "queued") and pending_since is None \
                and attempt_since is None:
            pending_since = ts
        if name in _PENDING_ENDERS and pending_since is not None:
            phase("pending", pending_since, ts, attempt=attempt_no + 1)
            pending_since = None
        if name == "running" and attempt_since is None:
            attempt_no = int(attrs.get("attempt") or attempt_no + 1)
            attempt_since = ts
        if name in _ATTEMPT_ENDERS and attempt_since is not None:
            phase(f"attempt-{attempt_no}", attempt_since, ts,
                  attempt=attempt_no, ended_by=name)
            attempt_since = None
        if name == "retrying" and pending_since is None and attempt_since is None:
            pending_since = ts  # backoff + requeue until it runs again
        if name == "promotion-started":
            promo_since = ts
        if name in ("promoted", "promotion-failed", "unpromoted"):
            # a settle without a recorded start — an unpromote (nothing
            # precedes it) or a failed unpromote — still gets an
            # instantaneous span so the event is covered, not a "gap"
            phase("promotion", ts if promo_since is None else promo_since,
                  ts, outcome=name)
            promo_since = None
        if name.startswith("serve-") and name != "serve-unloaded" \
                and serve_since is None:
            # any serve-plane event opens the phase: the fleet emits
            # replica-started events while the session is still being
            # assembled, BEFORE serve-loaded lands (docs/serving.md §Fleet)
            serve_since = ts
        if name == "serve-unloaded" and serve_since is not None:
            phase("serve", serve_since, ts)
            serve_since = None
    # close still-open phases at the root's end
    if pending_since is not None:
        phase("pending", pending_since, None, attempt=attempt_no + 1)
    if attempt_since is not None:
        phase(f"attempt-{attempt_no}", attempt_since, None, attempt=attempt_no)
    if promo_since is not None:
        phase("promotion", promo_since, None)
    if serve_since is not None:
        phase("serve", serve_since, None)

    # graft trainer spans under their attempt span (matched by attempt attr;
    # unmatched spans hang off the root so nothing is dropped)
    by_attempt = {
        s["attributes"].get("attempt"): s
        for s in spans
        if s["name"].startswith("attempt-")
    }
    trainer_ids = {s.get("span_id") for s in trainer_spans or []}
    for ts_span in trainer_spans or []:
        grafted = dict(ts_span)
        if trace_id:
            grafted["trace_id"] = trace_id
        pid = grafted.get("parent_span_id")
        attempt = by_attempt.get(
            grafted.get("attributes", {}).get("attempt")) or root
        if pid is None or pid not in trainer_ids:
            # no recorded parent, or the parent never landed — a kill loses
            # the spans still open (the crash-safe JSONL holds FINISHED
            # spans only), so a killed job's children would dangle off the
            # lost fit span: graft under the attempt/root instead
            grafted["parent_span_id"] = attempt["span_id"]
        begins = attempt["start_ns"]
        if grafted["start_ns"] < begins:
            # the trainer's process is older than the attempt as the
            # controller saw it — its start-up spans run from process start,
            # the monitor hears ``running`` a tick later, a warm worker
            # predates the job: cut to the attempt, and say by how much
            grafted["attributes"] = {
                **grafted.get("attributes", {}),
                "before_attempt_s": round(
                    (begins - grafted["start_ns"]) / 1e9, 3)}
            grafted["start_ns"] = begins
            if grafted.get("end_ns") is not None:
                grafted["end_ns"] = max(grafted["end_ns"], begins)
        spans.append(grafted)

    return {
        "trace_id": trace_id,
        "job_id": job.get("job_id"),
        "spans": spans,
        "problems": validate_trace(spans, events),
    }


def validate_trace(
    spans: list[dict[str, Any]],
    events: list[dict[str, Any]] | None = None,
) -> list[str]:
    """Structural checks: every parent resolves, every child's interval nests
    inside its parent's, and (when ``events`` are given) every event instant
    is covered by at least one non-root span — the "gap-free" property the
    e2e timeline test gates on.  Returns human-readable problems; [] = ok."""
    problems: list[str] = []
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        pid = s.get("parent_span_id")
        if pid is not None:
            parent = by_id.get(pid)
            if parent is None:
                problems.append(f"span {s['name']!r}: unknown parent {pid}")
                continue
            if s["start_ns"] < parent["start_ns"] - _EPS_NS:
                problems.append(
                    f"span {s['name']!r} starts before parent {parent['name']!r}"
                )
            if s.get("end_ns") is not None and parent.get("end_ns") is not None \
                    and s["end_ns"] > parent["end_ns"] + _EPS_NS:
                problems.append(
                    f"span {s['name']!r} ends after parent {parent['name']!r}"
                )
        if s.get("end_ns") is not None and s["end_ns"] + _EPS_NS < s["start_ns"]:
            problems.append(f"span {s['name']!r} ends before it starts")
    for e in events or []:
        ts_ns = _ns(e["ts"])
        covered = any(
            s.get("parent_span_id") is not None
            and s["start_ns"] - _EPS_NS <= ts_ns
            and (s.get("end_ns") is None or ts_ns <= s["end_ns"] + _EPS_NS)
            for s in spans
        )
        if not covered:
            problems.append(
                f"event {e['event']!r} at ts={e['ts']} not covered by any span"
            )
    return problems


async def export_trace(state, store, job_id: str) -> bool:
    """Assemble and persist ``trace/trace.json`` next to a settled job's
    artifacts — traces survive control-plane restarts and substrate cleanup.

    Best-effort and idempotent (``metadata.trace_exported`` is the latch), so
    EVERY path that settles a job calls it: the monitor's succeeded/failed
    branches, the supervisor's terminal-failure writes, the lease-kill path,
    and the API's cancel handler.  ``state``/``store`` are duck-typed
    (StateStore/ObjectStore) to keep this module dependency-free.
    """
    try:
        job = await state.get_job(job_id)
        if job is None or not job.status.is_final or not job.artifacts_uri:
            return False
        if job.metadata.get("trace_exported"):
            return False
        spans_uri = (
            f"{job.artifacts_uri}/{TRACE_DIRNAME}/{TRAINER_SPANS_FILENAME}"
        )
        trainer_spans = []
        if await store.exists(spans_uri):
            trainer_spans = parse_span_lines(await store.get_bytes(spans_uri))
        trace = build_trace(job.model_dump(mode="json"), trainer_spans)
        await store.put_bytes(
            f"{job.artifacts_uri}/{TRACE_DIRNAME}/trace.json",
            json.dumps(trace, indent=2).encode(),
        )
        await state.merge_job_metadata(job_id, {"trace_exported": True})
        return True
    except Exception:
        logger.debug("trace export failed for %s", job_id, exc_info=True)
        return False
