"""Tests for the observability layer (``finetune_controller_tpu/obs/`` —
docs/observability.md).

Layers covered:

* ``prom``   — histogram bucket/render semantics, the ObsHub registry,
  ``ftc_build_info`` / ``ftc_uptime_seconds``;
* ``phase``  — the trainer's step-phase clock (residual compute, reset);
* ``trace``  — span recorder crash-safety, trace assembly from the event
  timeline, the gap-free/nesting validator; the start-up log (imports by
  package, ``Trainer()``, every program compiled or loaded, the first step),
  its adoption by the fit's recorder;
* ``events`` — the trainer-side event log and the torn-line-tolerant parser;
* statestore — ``append_job_event`` idempotency on BOTH engines;
* trainer    — fit-loop integration (events/spans/phase columns on, all
  quiet with ``FTC_TRACE=0``) and the on-demand profiler window;
* monitor    — trainer-event ingest exactly-once, terminal trace export;
* supervisor — the HARD-PATH timeline e2e: a job that is preempted,
  resized, retried, and promoted has every transition event exactly once,
  in order, with monotonic timestamps, and its assembled span tree is
  gap-free with valid parent/child nesting (the ISSUE 9 acceptance gate);
* HTTP       — ``GET /jobs/{id}/timeline``, ``GET /jobs/{id}/trace``,
  ``POST /jobs/{id}/profile`` guards, ``GET /admin/resilience`` progress;
* backends   — ``deliver_file`` atomicity + sandbox containment;
* satellites — stream-logger trace/attempt prefix, heartbeat
  ``last_step``/``last_step_ms``.
"""

import asyncio
import json
import math
import os
import sys
import time

import pytest

from conftest import one_chip_catalog as _catalog
from conftest import run_async as run
from conftest import tiny_job_spec as _spec
from test_lifecycle import ScriptedBackend

from finetune_controller_tpu.controller import registry
from finetune_controller_tpu.controller.monitor import JobMonitor
from finetune_controller_tpu.controller.objectstore import LocalObjectStore
from finetune_controller_tpu.controller.schemas import (
    BackendJobReport,
    BackendJobState,
    DatabaseStatus,
    JobInput,
)
from finetune_controller_tpu.controller.statestore import StateStore
from finetune_controller_tpu.controller.task_builder import (
    DatasetInput,
    task_builder,
)
from finetune_controller_tpu.obs import (
    EventLogWriter,
    Histogram,
    ObsHub,
    PhaseClock,
    SpanRecorder,
    build_trace,
    make_event,
    new_trace_id,
    parse_event_lines,
    parse_span_lines,
    validate_trace,
)
from finetune_controller_tpu.resilience.policy import RetryPolicy
from finetune_controller_tpu.resilience.supervisor import RetrySupervisor


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# prom: histograms + the hub
# ---------------------------------------------------------------------------


def test_histogram_buckets_and_cumulative_render():
    h = Histogram("ftc_test_seconds", "help", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 100.0):
        h.observe(v)
    lines = h.render()
    assert "# TYPE ftc_test_seconds histogram" in lines
    # cumulative le series: 1, 3, 4, then +Inf catches the overflow
    assert 'ftc_test_seconds_bucket{le="0.1"} 1' in lines
    assert 'ftc_test_seconds_bucket{le="1"} 3' in lines
    assert 'ftc_test_seconds_bucket{le="10"} 4' in lines
    assert 'ftc_test_seconds_bucket{le="+Inf"} 5' in lines
    assert "ftc_test_seconds_count 5" in lines
    assert any(line.startswith("ftc_test_seconds_sum ") for line in lines)
    assert h.count() == 5


def test_histogram_labels_fixed_and_validated():
    h = Histogram("ftc_phase_ms", "help", (1, 10), label_names=("phase",))
    h.observe(0.5, phase="input")
    h.observe(5, phase="input")
    h.observe(5, phase="compute")
    with pytest.raises(ValueError):
        h.observe(1, wrong="x")
    with pytest.raises(ValueError):
        h.observe(1)  # missing the declared label
    lines = h.render()
    assert 'ftc_phase_ms_bucket{phase="compute",le="10"} 1' in lines
    assert 'ftc_phase_ms_bucket{phase="input",le="+Inf"} 2' in lines
    assert h.count(phase="input") == 2


def test_histogram_empty_renders_family_header_only():
    h = Histogram("ftc_idle", "help", (1,))
    lines = h.render()
    assert lines == ["# HELP ftc_idle help", "# TYPE ftc_idle histogram"]
    with pytest.raises(ValueError):
        Histogram("ftc_none", "help", ())  # at least one finite bucket


def test_obshub_observes_phase_columns_from_csv_row():
    hub = ObsHub()
    row = {
        "step": "10", "loss": "0.5",
        "phase_input_ms": "2.5", "phase_compute_ms": "7.5",
        "phase_checkpoint_ms": "", "phase_sync_ms": "garbage",
        "phase_eval_ms": None,
    }
    assert hub.observe_step_phases(row) == 2  # only the parseable columns
    assert hub.step_phase_ms.count(phase="input") == 1
    assert hub.step_phase_ms.count(phase="compute") == 1
    assert hub.step_phase_ms.count(phase="checkpoint") == 0
    # a row with no phase columns (pre-obs metrics CSV) is a no-op
    assert hub.observe_step_phases({"step": "1", "loss": "1.0"}) == 0


def test_obshub_process_info_lines():
    clock = FakeClock(100.0)
    hub = ObsHub(_clock=clock)
    clock.advance(42.0)
    lines = hub.render_process_info(
        process="monitor", version="0.1.0", backend='lo"cal'
    )
    joined = "\n".join(lines)
    assert 'ftc_build_info{process="monitor",version="0.1.0",' in joined
    assert 'backend="lo\\"cal"' in joined  # label escaping
    assert 'ftc_uptime_seconds{process="monitor"} 42.000' in joined


# ---------------------------------------------------------------------------
# phase: the step-phase clock
# ---------------------------------------------------------------------------


def test_phase_clock_residual_compute_and_reset():
    t = {"now": 0.0}
    clock = PhaseClock(_clock=lambda: t["now"])
    with clock.phase("input"):
        t["now"] += 0.2
    with clock.phase("checkpoint"):
        t["now"] += 0.3
    clock.add("sync", 0.1)
    # 4 steps over a 1.0s window: 0.6s measured, 0.4s residual compute
    row = clock.window_row(steps=4, wall_s=1.0)
    assert row["phase_input_ms"] == pytest.approx(50.0)
    assert row["phase_checkpoint_ms"] == pytest.approx(75.0)
    assert row["phase_sync_ms"] == pytest.approx(25.0)
    assert row["phase_eval_ms"] == 0.0
    assert row["phase_compute_ms"] == pytest.approx(100.0)
    assert set(row) == set(PhaseClock.columns())
    # the window reset: a second row starts from zero
    row2 = clock.window_row(steps=1, wall_s=0.0)
    assert all(v == 0.0 for v in row2.values())


def test_phase_clock_compute_clamped_at_zero():
    clock = PhaseClock(_clock=time.perf_counter)
    clock.add("input", 2.0)
    row = clock.window_row(steps=1, wall_s=1.0)  # measured > wall
    assert row["phase_compute_ms"] == 0.0


# ---------------------------------------------------------------------------
# trace: span recorder + parser
# ---------------------------------------------------------------------------


def test_span_recorder_writes_crash_safe_jsonl(tmp_path):
    rec = SpanRecorder(str(tmp_path), "t" * 32, attempt=2)
    with rec.span("checkpoint", step=40):
        pass
    span = rec.start("io")
    rec.finish(span, status="error", bytes=123)
    raw = (tmp_path / "trace" / "trainer.jsonl").read_text()
    # one flushed line per FINISHED span + a torn tail must not poison parse
    spans = parse_span_lines(raw + '{"span_id": "torn')
    assert [s["name"] for s in spans] == ["checkpoint", "io"]
    assert spans[0]["trace_id"] == "t" * 32
    assert spans[0]["attributes"]["step"] == 40
    assert spans[0]["attributes"]["attempt"] == 2
    assert spans[1]["status"] == "error"
    assert spans[1]["attributes"]["bytes"] == 123
    assert all(s["end_ns"] >= s["start_ns"] for s in spans)


def test_span_recorder_context_marks_error_on_exception(tmp_path):
    rec = SpanRecorder(str(tmp_path), new_trace_id())
    with pytest.raises(RuntimeError):
        with rec.span("fit"):
            raise RuntimeError("boom")
    spans = parse_span_lines((tmp_path / "trace" / "trainer.jsonl").read_text())
    assert spans[0]["status"] == "error"


def test_span_recorder_disabled_writes_nothing(tmp_path):
    for rec in (
        SpanRecorder(str(tmp_path), new_trace_id(), enabled=False),
        SpanRecorder(str(tmp_path), ""),  # no trace id -> disabled
    ):
        with rec.span("noop"):
            pass
    assert not (tmp_path / "trace").exists()


def test_span_recorder_swallows_write_failures(tmp_path):
    target = tmp_path / "trace"
    target.write_text("a file where the spans dir should go")
    rec = SpanRecorder(str(tmp_path), new_trace_id())
    with rec.span("doomed"):
        pass  # must not raise
    assert rec.write_failures == 1


# ---------------------------------------------------------------------------
# events: the trainer-side log
# ---------------------------------------------------------------------------


def test_event_log_writer_roundtrip_and_attribution(tmp_path):
    w = EventLogWriter(str(tmp_path), trace_id="abc123", attempt=3)
    assert w.emit("train-started", step=0)
    assert w.emit("checkpoint-committed", step=20, blocking=True)
    raw = (tmp_path / "events.jsonl").read_text()
    events = parse_event_lines(raw + "\n{torn")
    assert [e["event"] for e in events] == [
        "train-started", "checkpoint-committed",
    ]
    assert all(e["trace_id"] == "abc123" for e in events)
    assert all(e["attrs"]["attempt"] == 3 for e in events)
    assert events[1]["attrs"]["step"] == 20


def test_event_log_writer_disabled_and_failure_tolerant(tmp_path):
    w = EventLogWriter(str(tmp_path), enabled=False)
    assert not w.emit("train-started")
    assert not (tmp_path / "events.jsonl").exists()
    w2 = EventLogWriter(str(tmp_path / "missing" / "dir"))
    assert not w2.emit("train-started")  # unwritable: swallowed, reported
    assert w2.write_failures == 1


def test_make_event_filters_none_attrs():
    e = make_event("running", key="running:a1", attempt=1, slices=None)
    assert e["event"] == "running"
    assert e["key"] == "running:a1"
    assert e["attrs"] == {"attempt": 1}
    assert isinstance(e["ts"], float)


# ---------------------------------------------------------------------------
# statestore: exactly-once event append (both engines)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["jsonl", "sqlite"])
def test_append_job_event_idempotent(tmp_path, engine):
    from finetune_controller_tpu.controller.schemas import JobRecord

    async def main():
        state = StateStore(tmp_path / "state", backend=engine)
        await state.connect()
        await state.create_job(JobRecord(
            job_id="e-1", user_id="u", model_name="tiny-test-lora",
        ))
        assert await state.append_job_event(
            "e-1", make_event("running", key="running:a1", attempt=1)
        )
        # same idempotency key: dropped (the crash-retry convergence path)
        assert not await state.append_job_event(
            "e-1", make_event("running", key="running:a1", attempt=1)
        )
        # different key: appended
        assert await state.append_job_event(
            "e-1", make_event("running", key="running:a2", attempt=2)
        )
        # keyless events always append (trainer rows carry trainer:{idx})
        assert await state.append_job_event("e-1", make_event("succeeded"))
        job = await state.get_job("e-1")
        assert [e["event"] for e in job.events] == [
            "running", "running", "succeeded",
        ]
        # unknown job: refused, not crashed
        assert not await state.append_job_event(
            "nope", make_event("running", key="k")
        )
        await state.close()

    run(main())


@pytest.mark.parametrize("engine", ["jsonl", "sqlite"])
def test_append_job_events_batch_idempotent(tmp_path, engine):
    """The batch append (monitor ingest's one-write-per-tick path): per-item
    key dedupe against the stored list AND within the batch, survivors land
    in a single document write."""
    from finetune_controller_tpu.controller.schemas import JobRecord

    async def main():
        state = StateStore(tmp_path / "state", backend=engine)
        await state.connect()
        await state.create_job(JobRecord(
            job_id="e-2", user_id="u", model_name="tiny-test-lora",
        ))
        assert await state.append_job_event(
            "e-2", make_event("running", key="running:a1", attempt=1)
        )
        added = await state.append_job_events("e-2", [
            make_event("running", key="running:a1", attempt=1),  # stored dup
            make_event("checkpoint-committed", key="trainer:a1:0", step=10),
            make_event("checkpoint-committed", key="trainer:a1:0", step=10),
            make_event("checkpoint-committed", key="trainer:a1:1", step=20),
        ])
        assert added == 2
        job = await state.get_job("e-2")
        assert [e["event"] for e in job.events] == [
            "running", "checkpoint-committed", "checkpoint-committed",
        ]
        assert [
            e["attrs"]["step"] for e in job.events
            if e["event"] == "checkpoint-committed"
        ] == [10, 20]
        # empty batch and unknown jobs: no-ops, not crashes
        assert await state.append_job_events("e-2", []) == 0
        assert await state.append_job_events(
            "nope", [make_event("running", key="k")]
        ) == 0
        await state.close()

    run(main())


# ---------------------------------------------------------------------------
# trace assembly + the gap-free validator
# ---------------------------------------------------------------------------


def _job_doc(events, *, status="succeeded", end_time=None, trace_id="t" * 32):
    return {
        "job_id": "j-1",
        "status": status,
        "submitted_at": events[0]["ts"] if events else 0.0,
        "end_time": end_time,
        "metadata": {"trace_id": trace_id},
        "events": events,
    }


def test_build_trace_single_attempt_lifecycle():
    t0 = 100.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 5, key="running:a1", attempt=1),
        make_event("checkpoint-committed", ts=t0 + 20, step=10),
        make_event("succeeded", ts=t0 + 30, key="succeeded:a1"),
    ]
    trace = build_trace(_job_doc(events, end_time=t0 + 30))
    assert trace["problems"] == []
    names = [s["name"] for s in trace["spans"]]
    assert names[0] == "job"
    assert "pending" in names and "attempt-1" in names
    root = trace["spans"][0]
    for s in trace["spans"][1:]:
        assert s["parent_span_id"] == root["span_id"]
    pending = next(s for s in trace["spans"] if s["name"] == "pending")
    attempt = next(s for s in trace["spans"] if s["name"] == "attempt-1")
    # pending runs submit -> running; the attempt takes over from there
    assert pending.get("end_ns") == attempt["start_ns"]


def test_build_trace_grafts_trainer_spans_under_their_attempt():
    t0 = 50.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 1, key="running:a1", attempt=1),
        make_event("retrying", ts=t0 + 10, key="retrying:i0", attempt=1),
        make_event("running", ts=t0 + 20, key="running:a2", attempt=2),
        make_event("succeeded", ts=t0 + 30, key="succeeded:a2"),
    ]
    trainer_spans = [
        {
            "name": "checkpoint", "trace_id": "x", "span_id": "s" * 16,
            "parent_span_id": None,
            "start_ns": int((t0 + 22) * 1e9), "end_ns": int((t0 + 23) * 1e9),
            "status": "ok", "attributes": {"attempt": 2},
        },
        {
            "name": "orphan", "trace_id": "x", "span_id": "o" * 16,
            "parent_span_id": None,
            "start_ns": int((t0 + 5) * 1e9), "end_ns": int((t0 + 6) * 1e9),
            "status": "ok", "attributes": {},  # no attempt -> hangs off root
        },
    ]
    trace = build_trace(_job_doc(events, end_time=t0 + 30), trainer_spans)
    assert trace["problems"] == []
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["checkpoint"]["parent_span_id"] \
        == by_name["attempt-2"]["span_id"]
    assert by_name["orphan"]["parent_span_id"] == by_name["job"]["span_id"]
    assert by_name["checkpoint"]["trace_id"] == "t" * 32  # normalized


def test_build_trace_reparents_spans_whose_parent_never_landed():
    """A kill loses the spans still open (the crash-safe JSONL holds
    finished spans only), so a killed job's surviving children reference a
    fit span that never landed — they must re-graft under their attempt,
    not dangle as an 'unknown parent' problem."""
    t0 = 50.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 1, key="running:a1", attempt=1),
        make_event("cancelled", ts=t0 + 30, key="cancelled:1"),
    ]
    orphaned = {
        "name": "init", "trace_id": "x", "span_id": "i" * 16,
        "parent_span_id": "f" * 16,  # the lost (still-open) fit span
        "start_ns": int((t0 + 3) * 1e9), "end_ns": int((t0 + 8) * 1e9),
        "status": "ok", "attributes": {"attempt": 1},
    }
    trace = build_trace(
        _job_doc(events, status="cancelled", end_time=t0 + 30), [orphaned]
    )
    assert trace["problems"] == [], trace["problems"]
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["init"]["parent_span_id"] \
        == by_name["attempt-1"]["span_id"]


def test_build_trace_open_job_closes_phases_at_now():
    t0 = 10.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 1, key="running:a1", attempt=1),
    ]
    trace = build_trace(_job_doc(events, status="running"), now=t0 + 60)
    assert trace["problems"] == []
    attempt = next(s for s in trace["spans"] if s["name"] == "attempt-1")
    assert attempt["attributes"].get("in_progress") is True
    assert attempt["end_ns"] == int((t0 + 60) * 1e9)


def test_validate_trace_flags_structural_problems():
    tid = "t" * 32
    from finetune_controller_tpu.obs.trace import make_span

    root = make_span("job", tid, start_ns=0, end_ns=100)
    ok_child = make_span(
        "attempt-1", tid, start_ns=10, end_ns=90,
        parent_span_id=root["span_id"],
    )
    # child escapes its parent's interval
    escapee = make_span(
        "late", tid, start_ns=50, end_ns=int(1e9),
        parent_span_id=root["span_id"],
    )
    orphan = make_span("orphan", tid, start_ns=5, end_ns=6,
                       parent_span_id="f" * 16)
    problems = validate_trace([root, ok_child, escapee, orphan])
    assert any("ends after parent" in p for p in problems)
    assert any("unknown parent" in p for p in problems)
    # an event outside every non-root span is a GAP
    problems = validate_trace(
        [root, ok_child], [{"event": "preempted", "ts": 500.0}]
    )
    assert any("not covered" in p for p in problems)
    # the same event inside the attempt span is covered
    assert validate_trace(
        [root, ok_child],
        [{"event": "preempted", "ts": 50e-9}],
    ) == []


# ---------------------------------------------------------------------------
# trainer integration: the fit loop records events/spans/phase columns
# ---------------------------------------------------------------------------


def _tiny_trainer(total_steps=6, **overrides):
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=2))
    cfg = TrainConfig(
        mode="lora", learning_rate=1e-3, warmup_steps=1,
        total_steps=total_steps, batch_size=2, seq_len=16,
        log_every=3, checkpoint_every=1000, prefetch=0,
        heartbeat_interval_s=0, **overrides,
    )
    return Trainer(model_cfg, cfg), model_cfg


def test_fit_records_events_spans_and_phase_columns(tmp_path, monkeypatch):
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE_ID", "f" * 32)
    monkeypatch.setenv("FTC_ATTEMPT", "2")
    monkeypatch.delenv("FTC_TRACE", raising=False)
    trainer, model_cfg = _tiny_trainer()
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)

    events = parse_event_lines((tmp_path / "events.jsonl").read_text())
    names = [e["event"] for e in events]
    assert names[0] == "train-started"
    assert "checkpoint-committed" in names  # the final save
    assert names[-1] == "train-finished"
    assert all(e["trace_id"] == "f" * 32 for e in events)
    assert all(e["attrs"]["attempt"] == 2 for e in events)

    spans = parse_span_lines(
        (tmp_path / "trace" / "trainer.jsonl").read_text()
    )
    by_name = {s["name"]: s for s in spans}
    assert {"init", "checkpoint", "fit"} <= set(by_name)
    assert by_name["init"]["parent_span_id"] == by_name["fit"]["span_id"]
    assert by_name["fit"]["status"] == "ok"
    assert validate_trace(spans) == []

    import csv

    with open(tmp_path / "metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert rows, "no metrics rows logged"
    for col in PhaseClock.columns():
        assert col in rows[0], f"missing {col} in metrics header"
    # phases are per-step ms and the split is sane: nonnegative, with the
    # device step (compute) claiming a nonzero share
    total = sum(float(rows[0][c]) for c in PhaseClock.columns())
    assert total > 0
    assert float(rows[0]["phase_compute_ms"]) >= 0


def test_fit_trace_kill_switch(tmp_path, monkeypatch):
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE", "0")
    monkeypatch.setenv("FTC_TRACE_ID", "f" * 32)
    trainer, model_cfg = _tiny_trainer()
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert not (tmp_path / "events.jsonl").exists()
    assert not (tmp_path / "trace").exists()
    import csv

    with open(tmp_path / "metrics.csv", newline="") as f:
        header = next(csv.reader(f))
    assert not any(c.startswith("phase_") for c in header)


def test_consume_profile_request_retires_the_file(tmp_path):
    from finetune_controller_tpu.train.trainer import Trainer

    req = tmp_path / "profile_request.json"
    req.write_text(json.dumps({"steps": 3}))
    assert Trainer._consume_profile_request(str(req)) == 3
    assert not req.exists()  # retired either way
    assert (tmp_path / "profile_request.json.consumed").exists()
    # garbage payload: 0 steps, still retired (no per-step retrigger)
    req.write_text("{torn")
    assert Trainer._consume_profile_request(str(req)) == 0
    assert not req.exists()
    # out-of-range step counts are clamped
    req.write_text(json.dumps({"steps": 10**9}))
    assert Trainer._consume_profile_request(str(req)) == 1000


def test_fit_on_demand_profiler_window(tmp_path, monkeypatch):
    """The artifact-channel profile request arms jax.profiler mid-run:
    profile/ appears and the profile-captured event lands on the log."""
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE_ID", "p" * 32)
    monkeypatch.delenv("FTC_TRACE", raising=False)
    # deliver the request BEFORE the run: the first step consumes it
    (tmp_path / "profile_request.json").write_text(json.dumps({"steps": 2}))
    trainer, model_cfg = _tiny_trainer(total_steps=5)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert (tmp_path / "profile_request.json.consumed").exists()
    assert (tmp_path / "profile").is_dir()
    assert any((tmp_path / "profile").rglob("*")), "empty profiler trace"
    events = parse_event_lines((tmp_path / "events.jsonl").read_text())
    captured = [e for e in events if e["event"] == "profile-captured"]
    assert len(captured) == 1
    # armed before step 1: the 2-step window covers steps 1-2
    assert captured[0]["attrs"]["step"] == 2


def test_fit_on_demand_window_clamped_to_run_end(tmp_path, monkeypatch):
    """A window armed near the end of the run clamps to total_steps: the
    in-loop stop (and its profile-captured confirmation) still fires —
    an armed window must never complete silently via the finally-block."""
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE_ID", "p" * 32)
    monkeypatch.delenv("FTC_TRACE", raising=False)
    (tmp_path / "profile_request.json").write_text(json.dumps({"steps": 50}))
    trainer, model_cfg = _tiny_trainer(total_steps=4)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    events = parse_event_lines((tmp_path / "events.jsonl").read_text())
    captured = [e for e in events if e["event"] == "profile-captured"]
    assert [e["attrs"]["step"] for e in captured] == [4]
    assert any((tmp_path / "profile").rglob("*")), "empty profiler trace"


def test_fit_on_demand_window_does_not_starve_configured_trace(tmp_path, monkeypatch):
    """An on-demand window that spans the configured profile_start_step must
    not swallow the configured trace: it starts at the first free step
    after the on-demand capture ends, and BOTH windows land."""
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE_ID", "p" * 32)
    monkeypatch.delenv("FTC_TRACE", raising=False)
    # on-demand: armed before step 0, 3-step window [0, 3) — covering the
    # configured start (profile_start_step=1, 2 steps)
    (tmp_path / "profile_request.json").write_text(json.dumps({"steps": 3}))
    trainer, model_cfg = _tiny_trainer(
        total_steps=8, profile_steps=2, profile_start_step=1,
    )
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    events = parse_event_lines((tmp_path / "events.jsonl").read_text())
    captured = [e["attrs"]["step"] for e in events
                if e["event"] == "profile-captured"]
    # on-demand [0,3) closes at step 3; the configured 2-step window then
    # runs [3,5) instead of silently never firing
    assert captured == [3, 5]


def test_fit_on_demand_profiler_window_with_trace_off(tmp_path, monkeypatch):
    """FTC_TRACE=0 silences spans/events but NOT on-demand profiling: the
    delivered request is still consumed and the trace captured — otherwise
    POST /jobs/{id}/profile would 202 into a file nothing ever reads."""
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_TRACE", "0")
    monkeypatch.setenv("FTC_TRACE_ID", "p" * 32)
    monkeypatch.delenv("FTC_PROFILE", raising=False)
    (tmp_path / "profile_request.json").write_text(json.dumps({"steps": 2}))
    trainer, model_cfg = _tiny_trainer(total_steps=5)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert (tmp_path / "profile_request.json.consumed").exists()
    assert (tmp_path / "profile").is_dir()
    assert any((tmp_path / "profile").rglob("*")), "empty profiler trace"
    # the tracing kill switch still holds for spans and ordinary events —
    # but the capture CONFIRMATION is forced through (profiling is
    # decoupled from tracing, so its timeline evidence must be too)
    events = parse_event_lines((tmp_path / "events.jsonl").read_text())
    assert [e["event"] for e in events] == ["profile-captured"]
    assert not (tmp_path / "trace").exists()


def test_fit_profile_kill_switch(tmp_path, monkeypatch):
    """FTC_PROFILE=0 is profiling's own opt-out: the request file is left
    unconsumed and no trace is captured."""
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_PROFILE", "0")
    monkeypatch.setenv("FTC_TRACE_ID", "p" * 32)
    monkeypatch.delenv("FTC_TRACE", raising=False)
    (tmp_path / "profile_request.json").write_text(json.dumps({"steps": 2}))
    trainer, model_cfg = _tiny_trainer(total_steps=5)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert (tmp_path / "profile_request.json").exists()
    assert not (tmp_path / "profile").exists()


# ---------------------------------------------------------------------------
# trace: the start-up log (imports, backend, Trainer(), compiles, first step)
# ---------------------------------------------------------------------------


@pytest.fixture
def startup_log(monkeypatch):
    """A start-up log of the test's own where the process keeps its:
    a tier-1 worker's closed long ago, at its first step."""
    from finetune_controller_tpu.obs import trace

    log = trace.StartupLog(from_process_start=False).open()
    monkeypatch.setattr(trace, "STARTUP", log)
    yield log
    log.shutdown()


def _slow_packages(tmp_path, monkeypatch):
    """Two importable packages that sleep: ``ftcslowa`` 60 ms of its own and
    imports ``ftcslowb``, 30 ms."""
    for name, body in (
            ("ftcslowa", "import time\ntime.sleep(0.06)\nimport ftcslowb\n"),
            ("ftcslowb", "import time\ntime.sleep(0.03)\n")):
        (tmp_path / name).mkdir()
        (tmp_path / name / "__init__.py").write_text(body)
        monkeypatch.delitem(sys.modules, name, raising=False)
    monkeypatch.syspath_prepend(str(tmp_path))


def test_startup_log_spans_nest_and_the_root_covers_them(startup_log):
    log = startup_log
    with log.span("trainer.build", mode="lora") as outer:
        with log.span("trainer.build.rng") as inner:
            pass
        outer["attributes"]["n_layers"] = 2
    with pytest.raises(RuntimeError):
        with log.span("trainer.first_step"):
            raise RuntimeError("boom")
    assert not log.closed and log.root["end_ns"] is None
    log.close()
    log.close()     # the second close is nothing
    by_name = {s["name"]: s for s in log.spans}
    assert list(by_name) == ["trainer.build.rng", "trainer.build",
                             "trainer.first_step", "startup"]
    root = by_name["startup"]
    assert root is log.root and root["attributes"]["anchor"] == "package_import"
    assert by_name["trainer.build"]["parent_span_id"] == root["span_id"]
    assert inner["parent_span_id"] == outer["span_id"]
    assert by_name["trainer.first_step"]["status"] == "error"
    assert by_name["trainer.build"]["attributes"]["n_layers"] == 2
    for s in log.spans:
        assert root["start_ns"] <= s["start_ns"] <= s["end_ns"] <= root["end_ns"]
        assert {"import_s", "compile_s"} <= set(s["attributes"])
    assert validate_trace(log.spans) == []
    # closed, with no recorder: a later span goes nowhere, and nothing raises
    with log.span("trainer.build") as late:
        late["attributes"]["n_layers"] = 2
    assert len(log.spans) == 4


def test_startup_log_anchors_the_root_at_process_start():
    from finetune_controller_tpu.obs import trace

    log = trace.StartupLog()
    if log.root["attributes"]["anchor"] == "process":   # /proc and CLOCK_BOOTTIME
        assert log.root["start_ns"] < trace.STARTUP.root["start_ns"] + 10**9
        assert time.time_ns() - log.root["start_ns"] < 3600 * 10**9
    else:
        assert log.root["attributes"]["anchor"] == "package_import"
    # the process's own was opened by the package's import
    assert trace.STARTUP.root["name"] == "startup"


def test_the_programs_first_devices_call_is_the_backend_span(startup_log):
    from finetune_controller_tpu import platform

    found = platform.devices()
    assert platform.devices() == found      # the second call: no second span
    assert platform.device_report()["count"] == len(found)
    backend = [s for s in startup_log.spans if s["name"] == "startup.backend"]
    assert len(backend) == 1
    attrs = backend[0]["attributes"]
    assert (attrs["platform"], attrs["count"]) == ("cpu", len(found))
    assert attrs["kind"] == found[0].device_kind
    assert attrs["already_up"] in (True, False)
    assert backend[0]["parent_span_id"] == startup_log.root["span_id"]
    startup_log.close()
    assert platform.devices() == found      # closed: JAX's own call, no span
    assert startup_log.summary()["backend"] >= 0


def test_startup_log_times_imports_exclusive_by_package(
        startup_log, tmp_path, monkeypatch):
    log = startup_log
    _slow_packages(tmp_path, monkeypatch)
    with log.span("trainer.build") as build:
        import ftcslowa  # noqa: F401
    with log.span("trainer.build.guards") as idle:
        pass
    log.close()
    root = log.root["attributes"]
    by_package = root["import_by_package"]
    # a package's own modules, not what they import from another: parts add up
    assert 0.06 <= by_package["ftcslowa"] < 0.09
    assert 0.03 <= by_package["ftcslowb"] < 0.06
    assert sum(by_package.values()) == pytest.approx(root["import_s"], abs=1e-4)
    named = sum(v for k, v in by_package.items() if k != "(other)")
    assert named >= 0.9 * root["import_s"]
    assert all(k in by_package for k, v in log._by_package.items() if v > 0.05)
    # a span's import_s is the imports that ran inside it
    assert build["attributes"]["import_s"] == pytest.approx(
        by_package["ftcslowa"] + by_package["ftcslowb"], abs=5e-3)
    assert idle["attributes"]["import_s"] == 0.0
    # the modules were handed back to their real loaders
    for name in ("ftcslowa", "ftcslowb"):
        module = sys.modules[name]
        assert type(module.__spec__.loader).__name__ == "SourceFileLoader"
        assert module.__loader__ is module.__spec__.loader


def test_first_step_closes_the_log_and_frees_the_import_machinery(startup_log):
    from finetune_controller_tpu.data import synthetic_batches

    log = startup_log
    assert log._observer in sys.meta_path
    trainer, model_cfg = _tiny_trainer()
    state = trainer.init_state()
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    assert not log.closed
    state, _ = trainer.step(state, next(batches))
    assert log.closed and log._observer not in sys.meta_path
    n = len(log.spans)
    trainer.step(state, next(batches))      # the cached path: nothing recorded
    assert len(log.spans) == n and log.spans[-1] is log.root
    by_name = {s["name"]: s for s in log.spans}
    assert {"startup", "trainer.build", "trainer.build.rng",
            "trainer.build.abstract_state", "trainer.build.shardings",
            "trainer.build.optimizer", "trainer.build.guards",
            "trainer.first_step", "compile"} <= set(by_name)
    build = by_name["trainer.build"]["attributes"]
    assert (build["n_layers"], build["scan_layers"], build["mode"]) == (
        model_cfg.n_layers, model_cfg.scan_layers, "lora")
    assert by_name["trainer.first_step"]["attributes"]["step_programs"] == 1
    step = [s for s in log.spans if s["name"] == "compile"
            and s["attributes"]["step"]]
    assert [s["attributes"]["fun_name"] for s in step] == ["jit(_train_step)"]
    assert step[0]["parent_span_id"] == by_name["trainer.first_step"]["span_id"]
    attrs = step[0]["attributes"]
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0 and attrs["backend_s"] > 0
    assert attrs["cache"] in ("hit", "miss")
    assert log.step_compiles() == [log.programs["jit(_train_step)"]]
    # every program has a name; the small ones share one span and are counted
    assert all(s["attributes"]["fun_name"] for s in log.spans
               if s["name"] == "compile")
    assert all(s["attributes"]["count"] >= 1 for s in log.spans
               if s["name"] == "compile.small")
    assert validate_trace(log.spans) == []


def _assembled(tmp_path, log):
    """The job's trace as ``GET /jobs/{id}/trace`` assembles it: the
    controller saw ``running`` just before the log's root starts."""
    t0 = log.root["start_ns"] / 1e9
    events = [
        make_event("submitted", ts=t0 - 2, key="submitted:1"),
        make_event("running", ts=t0 - 1, key="running:a1", attempt=1),
        make_event("succeeded", ts=time.time() + 1, key="succeeded:a1"),
    ]
    spans = parse_span_lines(
        (tmp_path / "trace" / "trainer.jsonl").read_text())
    return build_trace(
        _job_doc(events, end_time=time.time() + 1, trace_id="f" * 32), spans)


def test_fit_adopts_the_startup_log_under_its_fit_span(
        startup_log, tmp_path, monkeypatch):
    """A tiny-preset local job: ``startup`` (imports by package),
    ``trainer.build``, the step's ``compile`` and ``trainer.first_step`` under
    attempt-1 -> fit in the assembled trace, gap-free from the log's start."""
    from finetune_controller_tpu.data import synthetic_batches

    log = startup_log
    monkeypatch.setenv("FTC_TRACE_ID", "f" * 32)
    monkeypatch.setenv("FTC_ATTEMPT", "1")
    monkeypatch.delenv("FTC_TRACE", raising=False)
    trainer, model_cfg = _tiny_trainer()
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert log.closed

    trace = _assembled(tmp_path, log)
    assert trace["problems"] == []
    by_name = {s["name"]: s for s in trace["spans"]}
    by_id = {s["span_id"]: s for s in trace["spans"]}

    def path(name):
        out, s = [], by_name[name]
        while s["parent_span_id"] is not None:
            s = by_id[s["parent_span_id"]]
            out.append(s["name"])
        return out

    assert path("startup") == ["fit", "attempt-1", "job"]
    assert path("trainer.build") == ["startup", "fit", "attempt-1", "job"]
    assert path("trainer.first_step") == ["startup", "fit", "attempt-1", "job"]
    assert path("init") == ["fit", "attempt-1", "job"]
    step = next(s for s in trace["spans"] if s["name"] == "compile"
                and s["attributes"]["step"])
    assert by_id[step["parent_span_id"]]["name"] == "trainer.first_step"
    start = by_name["startup"]
    assert start["attributes"]["import_s"] >= 0
    assert isinstance(start["attributes"]["import_by_package"], dict)
    # fit starts where the log did: no gap between the attempt and the program
    assert by_name["fit"]["start_ns"] == start["start_ns"] == log.root["start_ns"]
    # every adopted span is the job's: trace id, service and attempt filled in
    adopted = [s for s in trace["spans"] if s["name"].startswith(
        ("startup", "trainer.", "compile"))]
    assert all(s["trace_id"] == "f" * 32 and s["attributes"]["attempt"] == 1
               and s["attributes"]["service"] == "trainer" for s in adopted)
    # ... and the events say what start-up cost and what the step's compile was
    events = {e["event"]: e["attrs"] for e in parse_event_lines(
        (tmp_path / "events.jsonl").read_text())}
    assert set(events["train-started"]["startup_s"]) == {
        "import", "backend", "trainer_build"}
    assert by_name["startup.backend"]["attributes"]["platform"] == "cpu"
    finished = events["train-finished"]["step_compiles"]
    assert [p["fun_name"] for p in finished] == ["jit(_train_step)"]
    assert finished[0]["count"] == 1 and finished[0]["backend_s"] > 0

    # a second fit in the process: the log is closed, nothing is adopted
    # again, and its trainer's own build goes to the live recorder
    second = tmp_path / "second"
    trainer2, _ = _tiny_trainer()
    trainer2.fit(batches, str(second), resume=False)
    names = [s["name"] for s in parse_span_lines(
        (second / "trace" / "trainer.jsonl").read_text())]
    assert "startup" not in names and "trainer.first_step" not in names
    assert {"fit", "init"} <= set(names)


def test_second_trainer_under_a_live_recorder_writes_its_build_there(
        startup_log, tmp_path):
    log = startup_log
    log.close()
    rec = SpanRecorder(str(tmp_path), "t" * 32, attempt=3, startup=log)
    fit = rec.start("fit")
    _tiny_trainer()
    rec.finish(fit)
    spans = parse_span_lines((tmp_path / "trace" / "trainer.jsonl").read_text())
    by_name = {s["name"]: s for s in spans}
    assert by_name["trainer.build"]["parent_span_id"] == fit["span_id"]
    assert by_name["trainer.build.rng"]["parent_span_id"] \
        == by_name["trainer.build"]["span_id"]
    assert by_name["trainer.build"]["attributes"]["attempt"] == 3
    assert validate_trace(spans) == []
    assert [s["name"] for s in log.spans] == ["startup"]    # frozen
    del rec     # the recorder gone, the next build goes nowhere
    _tiny_trainer()
    assert len(parse_span_lines(
        (tmp_path / "trace" / "trainer.jsonl").read_text())) == len(spans)


def test_fit_with_trace_off_writes_nothing_and_keeps_the_log(
        startup_log, tmp_path, monkeypatch):
    """``TrainConfig.trace=False`` (the benchmark's) and ``FTC_TRACE=0`` stop
    the JSONL flush, not the in-memory log a benchmark reads in-process."""
    from finetune_controller_tpu.data import synthetic_batches

    log = startup_log
    monkeypatch.setenv("FTC_TRACE_ID", "f" * 32)
    trainer, model_cfg = _tiny_trainer(trace=False)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path), resume=False)
    assert not (tmp_path / "trace").exists()
    assert not (tmp_path / "events.jsonl").exists()
    assert log.closed and log.spans[-1] is log.root
    assert {"trainer.build", "trainer.first_step", "compile"} <= {
        s["name"] for s in log.spans}
    assert log.root["attributes"]["import_s"] >= 0


def test_build_trace_cuts_spans_older_than_their_attempt_to_it():
    """A warm worker's process predates the job, and the monitor hears
    ``running`` a tick after a cold one began: what the trainer did before
    the attempt as the controller saw it is cut to the attempt's start."""
    t0 = 50.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 2, key="running:a1", attempt=1),
        make_event("succeeded", ts=t0 + 30, key="succeeded:a1"),
    ]

    def span(name, sid, parent, start, end):
        return {"name": name, "trace_id": "x", "span_id": sid * 16,
                "parent_span_id": parent and parent * 16,
                "start_ns": int((t0 + start) * 1e9),
                "end_ns": int((t0 + end) * 1e9), "status": "ok",
                "attributes": {"attempt": 1}}

    trace = build_trace(_job_doc(events, end_time=t0 + 30), [
        span("fit", "f", None, -100, 29),
        span("startup", "s", "f", -100, 12),
        span("startup.backend", "b", "s", -99, -90),    # all of it before
        span("trainer.build", "t", "s", 1, 8),          # straddles
        span("init", "i", "f", 12, 13),                 # after: untouched
    ])
    assert trace["problems"] == []
    by_name = {s["name"]: s for s in trace["spans"]}
    begins = by_name["attempt-1"]["start_ns"]
    assert by_name["fit"]["start_ns"] == by_name["startup"]["start_ns"] == begins
    assert by_name["fit"]["attributes"]["before_attempt_s"] == 102.0
    backend = by_name["startup.backend"]
    assert backend["start_ns"] == backend["end_ns"] == begins
    assert backend["attributes"]["before_attempt_s"] == 101.0
    assert by_name["trainer.build"]["attributes"]["before_attempt_s"] == 1.0
    assert by_name["trainer.build"]["end_ns"] == int((t0 + 8) * 1e9)
    assert "before_attempt_s" not in by_name["init"]["attributes"]


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from finetune_controller_tpu.obs import trace

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def cache_probe(x):
    return jnp.tanh(x @ x.T).sum()

jax.jit(cache_probe)(jnp.ones((64, 64))).block_until_ready()
import json
print(json.dumps(trace.STARTUP.programs["jit(cache_probe)"]))
"""


def test_compile_span_says_miss_then_hit_over_two_processes(tmp_path):
    """One temporary cache directory, two processes: the first compiles the
    program and writes it, the second loads it — each says so by name."""
    import subprocess

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PYTHONPATH": os.pathsep.join(
               [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
               + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    heard = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        heard.append(json.loads(r.stdout.strip().splitlines()[-1]))
    first, second = heard
    assert first["fun_name"] == second["fun_name"] == "jit(cache_probe)"
    assert (first["cache"], first.get("cache_written")) == ("miss", True)
    assert "cache_load_s" not in first
    assert second["cache"] == "hit" and second["cache_load_s"] > 0
    assert second["backend_s"] >= second["cache_load_s"]
    for attrs in heard:
        assert attrs["step"] is False and attrs["count"] == 1
        assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0


# ---------------------------------------------------------------------------
# monitor: trainer-event ingest + terminal trace export
# ---------------------------------------------------------------------------


async def _plane(tmp_path, *, clock, max_attempts=4, obs=None):
    registry.reset()
    registry.load_builtin_models()
    state = StateStore(tmp_path / "state")
    store = LocalObjectStore(tmp_path / "objects")
    backend = ScriptedBackend()
    catalog = _catalog()
    supervisor = RetrySupervisor(
        state, backend, catalog,
        policy=RetryPolicy(
            max_attempts=max_attempts, base_delay_s=5.0, max_delay_s=5.0,
            seed=0,
        ),
        obs=obs,
        _clock=clock,
    )
    monitor = JobMonitor(
        state, store, backend, interval_s=0.1, supervisor=supervisor, obs=obs,
    )
    await state.connect()
    return state, store, backend, catalog, supervisor, monitor


async def _submit(state, store, backend, catalog, job_id="o-1",
                  user_id="u"):
    spec = _spec()
    job = JobInput(
        job_id=job_id, user_id=user_id, model_name="tiny-test-lora",
        device="chip-1", arguments=spec.training_arguments.model_dump(),
    )
    await task_builder(
        job, spec, DatasetInput(),
        state=state, store=store, backend=backend, catalog=catalog,
        datasets_bucket="datasets", artifacts_bucket="artifacts",
    )
    return job


def test_monitor_ingests_trainer_events_exactly_once(tmp_path):
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        lines = [
            json.dumps(make_event("train-started", ts=1.0, step=0)),
            json.dumps(make_event("checkpoint-committed", ts=2.0, step=10)),
        ]
        await store.put_bytes(
            f"{job.artifacts_uri}/events.jsonl",
            ("\n".join(lines) + "\n").encode(),
        )
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        await monitor.tick()
        await monitor.tick()  # second pass must not duplicate
        job = await state.get_job("o-1")
        trainer_events = [
            e for e in job.events
            if e["event"] in ("train-started", "checkpoint-committed")
        ]
        assert [e["event"] for e in trainer_events] == [
            "train-started", "checkpoint-committed",
        ]
        assert job.metadata["obs_events_ingested"] == 2
        # the trainer appends a new line; only IT is ingested
        lines.append(
            json.dumps(make_event("checkpoint-committed", ts=3.0, step=20))
        )
        await store.put_bytes(
            f"{job.artifacts_uri}/events.jsonl",
            ("\n".join(lines) + "\n").encode(),
        )
        await monitor.tick()
        job = await state.get_job("o-1")
        commits = [
            e for e in job.events if e["event"] == "checkpoint-committed"
        ]
        assert [e["attrs"]["step"] for e in commits] == [10, 20]
        assert job.metadata["obs_events_ingested"] == 3

    run(main())


def test_monitor_ingest_survives_events_file_restart(tmp_path):
    """A retry's fresh sandbox on a backend that does not stage events.jsonl
    back (e.g. a k8s pod) re-begins the file at line 0 and the sidecar
    overwrites the stored copy.  The ingest must neither stall (watermark
    above the line count) nor drop the new attempt's rows to positional key
    collisions with the old attempt's."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        uri = f"{job.artifacts_uri}/events.jsonl"
        a1 = [
            json.dumps(make_event("train-started", ts=1.0, step=0, attempt=1)),
            json.dumps(make_event(
                "checkpoint-committed", ts=2.0, step=10, attempt=1,
            )),
        ]
        await store.put_bytes(uri, ("\n".join(a1) + "\n").encode())
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        await monitor.tick()
        job = await state.get_job("o-1")
        assert job.metadata["obs_events_ingested"] == 2
        # attempt 2's pod starts a FRESH file, shorter than the watermark
        a2 = [json.dumps(make_event(
            "train-started", ts=9.0, step=10, attempt=2,
        ))]
        await store.put_bytes(uri, (a2[0] + "\n").encode())
        await monitor.tick()
        job = await state.get_job("o-1")
        starts = [e for e in job.events if e["event"] == "train-started"]
        assert [e["attrs"]["attempt"] for e in starts] == [1, 2]
        assert job.metadata["obs_events_ingested"] == 1  # the new file's count
        # the new attempt keeps appending: new rows land exactly once
        a2.append(json.dumps(make_event(
            "checkpoint-committed", ts=10.0, step=20, attempt=2,
        )))
        await store.put_bytes(uri, ("\n".join(a2) + "\n").encode())
        await monitor.tick()
        await monitor.tick()
        job = await state.get_job("o-1")
        commits = [
            e for e in job.events if e["event"] == "checkpoint-committed"
        ]
        assert [e["attrs"]["step"] for e in commits] == [10, 20]
        # a restarted file that has already GROWN past the watermark (slow
        # sync cadence): only the first-line fingerprint can detect it —
        # a length check would silently drop the first rows
        a3 = [
            json.dumps(make_event("train-started", ts=20.0, step=20, attempt=3)),
            json.dumps(make_event(
                "checkpoint-committed", ts=21.0, step=30, attempt=3,
            )),
            json.dumps(make_event(
                "checkpoint-committed", ts=22.0, step=40, attempt=3,
            )),
        ]
        await store.put_bytes(uri, ("\n".join(a3) + "\n").encode())
        await monitor.tick()
        job = await state.get_job("o-1")
        starts = [e for e in job.events if e["event"] == "train-started"]
        assert [e["attrs"]["attempt"] for e in starts] == [1, 2, 3]
        commits = [
            e for e in job.events if e["event"] == "checkpoint-committed"
        ]
        assert [e["attrs"]["step"] for e in commits] == [10, 20, 30, 40]

    run(main())


def test_monitor_ingest_is_best_effort_and_poison_tolerant(tmp_path):
    """The module contract — the timeline must never stall reconciliation:
    a garbage ts in a row must not raise every tick, and a failing store
    write aborts only THIS job's ingest (retried next tick), not the pass."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        uri = f"{job.artifacts_uri}/events.jsonl"
        poison = dict(make_event("train-started", attempt=1))
        poison["ts"] = "not-a-number"
        await store.put_bytes(
            uri, (json.dumps(poison) + "\n").encode(),
        )
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        # a transient write failure must not escape the ingest
        real_batch = state.append_job_events
        fail_once = {"armed": True}

        async def flaky_batch(jid, evs):
            if fail_once.pop("armed", None):
                raise IOError("injected statestore outage")
            return await real_batch(jid, evs)

        state.append_job_events = flaky_batch
        await monitor.tick()  # write fails; tick must complete anyway
        job = await state.get_job("o-1")
        assert "obs_events_ingested" not in job.metadata
        await monitor.tick()  # retried: poison ts lands with a now-stamp
        job = await state.get_job("o-1")
        starts = [e for e in job.events if e["event"] == "train-started"]
        assert len(starts) == 1
        assert isinstance(starts[0]["ts"], float)
        assert job.metadata["obs_events_ingested"] == 1

    run(main())


def test_monitor_ingest_batches_writes_and_skips_unchanged_reads(tmp_path):
    """Per-tick cost of the trainer-event ingest: all new rows of a tick fold
    into ONE batched document write, and an unchanged events.jsonl costs a
    stat — not a read — on every subsequent tick."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        uri = f"{job.artifacts_uri}/events.jsonl"
        lines = [json.dumps(make_event("train-started", ts=1.0, attempt=1))]
        lines += [
            json.dumps(make_event(
                "checkpoint-committed", ts=float(i), step=i * 10, attempt=1,
            ))
            for i in range(1, 5)
        ]
        await store.put_bytes(uri, ("\n".join(lines) + "\n").encode())
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        reads: list[str] = []
        batches: list[int] = []
        singles: list[dict] = []
        real_get, real_batch, real_single = (
            store.get_bytes, state.append_job_events, state.append_job_event,
        )

        async def counting_get(u):
            if u.endswith("events.jsonl"):
                reads.append(u)
            return await real_get(u)

        async def counting_batch(jid, evs):
            batches.append(len(evs))
            return await real_batch(jid, evs)

        async def counting_single(jid, ev):
            singles.append(ev)
            return await real_single(jid, ev)

        store.get_bytes = counting_get
        state.append_job_events = counting_batch
        state.append_job_event = counting_single
        await monitor.tick()
        assert batches == [5], "all five rows must land in one write"
        assert not [
            e for e in singles
            if str(e.get("key", "")).startswith("trainer:")
        ], "trainer rows must not go through the per-event path"
        assert len(reads) == 1
        await monitor.tick()  # unchanged file: stat short-circuit, no read
        await monitor.tick()
        assert len(reads) == 1
        assert batches == [5]

    run(main())


def test_supervisor_events_use_dispatch_numbering_after_resize(tmp_path):
    """A resize is budget-exempt but still a dispatch: after resize-then-
    preempt, the retrying events must name dispatches 1 and 2 — the same
    numbering as running/FTC_ATTEMPT/trainer spans.  (The budget count,
    which excludes resizes, would label BOTH retrying events attempt=1.)"""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        job = await state.get_job("o-1")
        # dispatch 1 ends in a scheduler resize (budget-exempt)
        assert await sup.on_job_failed(
            job, exit_code=143, message="resized by scheduler",
            resize_to=1, report_metadata={"resize_kind": "shrink"},
        )
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        job = await state.get_job("o-1")
        # dispatch 2 ends in a genuine preemption (burns budget attempt 1)
        assert await sup.on_job_failed(
            job, exit_code=143, message="preempted",
            report_metadata={"preempted": True, "preempted_by": "hi"},
        )
        job = await state.get_job("o-1")
        retries = [e for e in job.events if e["event"] == "retrying"]
        assert [e["attrs"]["attempt"] for e in retries] == [1, 2]

    run(main())


def test_phase_histograms_not_double_counted_across_resume_truncation(tmp_path):
    """Crash-resume truncates replayed rows from the metrics CSV (the
    MetricsWriter replay-drop) and the trainer then re-logs those windows:
    the step-phase histograms must observe each step exactly once — the
    stored record COUNT is not a safe watermark across the truncation."""
    async def main():
        clock = FakeClock()
        obs = ObsHub()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock, obs=obs
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        uri = f"{job.artifacts_uri}/metrics.csv"
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )

        def csv_for(steps):
            head = "step,loss,phase_input_ms\n"
            return (
                head + "".join(f"{s},1.0,{5.0 + s}\n" for s in steps)
            ).encode()

        def observed():
            return sum(obs.step_phase_ms._counts.get(("input",), []))

        await store.put_bytes(uri, csv_for(range(1, 11)))
        await monitor.tick()
        assert observed() == 10
        # crash + resume from the step-5 checkpoint: rows 6-10 truncated
        await store.put_bytes(uri, csv_for(range(1, 6)))
        await monitor.tick()
        assert observed() == 10
        # the resumed attempt re-logs steps 6-10 with fresh timings — same
        # steps, so they must NOT observe a second time
        await store.put_bytes(uri, csv_for(range(1, 11)))
        await monitor.tick()
        assert observed() == 10
        # genuinely new steps still observe
        await store.put_bytes(uri, csv_for(range(1, 13)))
        await monitor.tick()
        assert observed() == 12

    run(main())


def test_monitor_exports_trace_on_success(tmp_path):
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        await monitor.tick()
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.SUCCEEDED,
            start_time=1.0, completion_time=9.0,
        )
        await monitor.tick()
        job = await state.get_job("o-1")
        assert job.status is DatabaseStatus.SUCCEEDED
        raw = await store.get_bytes(f"{job.artifacts_uri}/trace/trace.json")
        trace = json.loads(raw)
        assert trace["trace_id"] == job.metadata["trace_id"]
        assert trace["problems"] == []
        assert {"job", "pending", "attempt-1"} <= {
            s["name"] for s in trace["spans"]
        }
        assert job.metadata["trace_exported"] is True

    run(main())


def test_build_trace_covers_promotion_settles_without_start():
    """An unpromote (and a failed unpromote) appends a settle event with no
    ``promotion-started`` before it — the trace must still cover it instead
    of reporting a healthy lifecycle as gap-ridden."""
    t0 = 100.0
    events = [
        make_event("submitted", ts=t0, key="submitted:1"),
        make_event("running", ts=t0 + 1, key="running:a1", attempt=1),
        make_event("succeeded", ts=t0 + 10, key="succeeded:a1"),
        make_event("promotion-started", ts=t0 + 20, key="ps:1"),
        make_event("promoted", ts=t0 + 25, key="p:1"),
        make_event("unpromoted", ts=t0 + 40, key="u:1"),
        # a later unpromote attempt that fails also settles start-less
        make_event("promotion-failed", ts=t0 + 50, key="pf:1"),
    ]
    trace = build_trace(_job_doc(events, end_time=t0 + 10))
    assert trace["problems"] == [], trace["problems"]
    promos = [s for s in trace["spans"] if s["name"] == "promotion"]
    assert [s["attributes"]["outcome"] for s in promos] == [
        "promoted", "unpromoted", "promotion-failed",
    ]
    # the started->promoted pair brackets a real interval; the start-less
    # settles are instantaneous
    assert promos[0]["end_ns"] - promos[0]["start_ns"] == int(5e9)
    assert promos[1]["end_ns"] == promos[1]["start_ns"]


def test_monitor_ingest_tolerates_reserved_and_corrupt_attr_rows(tmp_path):
    """events.jsonl is untrusted input: attrs shadowing ``make_event``'s own
    parameters must be dropped (not raise a TypeError that aborts the tick),
    and a row whose attempt is NaN is skipped without losing its neighbors."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        job = await state.get_job("o-1")
        lines = [
            json.dumps({
                "ts": 1.0, "event": "train-started",
                "attrs": {"ts": 99.0, "event": "zap", "key": "boom", "step": 0},
            }),
            json.dumps({
                "ts": 2.0, "event": "checkpoint-committed",
                "attrs": {"attempt": float("nan"), "step": 10},
            }),
            json.dumps(make_event("train-finished", ts=3.0, step=20)),
        ]
        await store.put_bytes(
            f"{job.artifacts_uri}/events.jsonl",
            ("\n".join(lines) + "\n").encode(),
        )
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        await monitor.tick()  # must not raise
        job = await state.get_job("o-1")
        by_name = {e["event"]: e for e in job.events}
        started = by_name["train-started"]
        assert started["ts"] == 1.0  # the file-level ts, not the attr
        assert started["attrs"]["step"] == 0
        assert "ts" not in started["attrs"] and "key" not in started["attrs"]
        # the NaN-attempt row is dropped; its neighbor still lands
        assert "checkpoint-committed" not in by_name
        assert by_name["train-finished"]["attrs"]["step"] == 20
        assert job.metadata["obs_events_ingested"] == 3

    run(main())


def test_monitor_exports_trace_for_job_settled_outside_report_loop(tmp_path):
    """A job that went terminal outside the succeeded/failed report branches
    (user cancel racing the tick) still gets its trace exported while its
    backend report lingers."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)
        await state.append_job_event(
            "o-1", make_event("cancelled", key="cancelled:1")
        )
        await state.update_job_status(
            "o-1", DatabaseStatus.CANCELLED, end_time=5.0, queue_position=None
        )
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING, start_time=1.0,
        )
        await monitor.tick()
        job = await state.get_job("o-1")
        assert job.metadata.get("trace_exported") is True
        trace = json.loads(
            await store.get_bytes(f"{job.artifacts_uri}/trace/trace.json")
        )
        assert trace["problems"] == [], trace["problems"]

    run(main())


def test_supervisor_terminal_failure_exports_trace(tmp_path):
    """Terminal FAILED writes on paths the report loop never revisits (lease
    kill, sweep, resubmit failures) flow through the supervisor's
    ``on_terminal`` hook, which the monitor wires to its trace export."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock, max_attempts=1
        )
        assert sup.on_terminal is not None  # wired by JobMonitor.__init__
        await _submit(state, store, backend, catalog)
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        job = await state.get_job("o-1")
        retried = await sup.on_job_failed(
            job, exit_code=1, message="stuck; killed by the liveness lease"
        )
        assert retried is False
        job = await state.get_job("o-1")
        assert job.status is DatabaseStatus.FAILED
        assert job.metadata.get("trace_exported") is True
        assert await store.exists(f"{job.artifacts_uri}/trace/trace.json")

    run(main())


def test_restart_recovery_events_get_fresh_keys_and_crash_retry_dedupes(tmp_path):
    """A pod restart inside ONE attempt produces RESTARTING -> RUNNING ->
    RESTARTING transitions that must each land on the timeline (per-attempt
    keys alone would fold them into the first occurrence) — while a monitor
    crash between the event append and the status write still dedupes to
    exactly one event on the re-observed transition."""
    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        await _submit(state, store, backend, catalog)

        async def observe(state_):
            backend.reports["o-1"] = BackendJobReport(
                job_id="o-1", state=state_, start_time=1.0,
            )
            await monitor.tick()

        await observe(BackendJobState.RUNNING)
        await observe(BackendJobState.RESTARTING)
        await observe(BackendJobState.RUNNING)
        await observe(BackendJobState.RESTARTING)
        job = await state.get_job("o-1")
        names = [e["event"] for e in job.events]
        assert names == [
            "submitted", "running", "restarting", "running", "restarting",
        ]
        keys = [e["key"] for e in job.events if "key" in e]
        assert len(keys) == len(set(keys))
        # crash-retry: the event for the NEXT transition was appended but the
        # process died before the status write — the re-observed transition
        # reuses the same seq-scoped key and the duplicate is dropped
        seq = job.metadata["obs_transition_seq"]
        await state.append_job_event(
            "o-1",
            make_event("running", key=f"running:a1:t{seq}", attempt=1),
        )
        await observe(BackendJobState.RUNNING)
        job = await state.get_job("o-1")
        assert [e["event"] for e in job.events].count("running") == 3
        assert job.status is DatabaseStatus.RUNNING

    run(main())


def test_cancel_endpoint_exports_trace(tmp_path):
    """POST /jobs/{id}/cancel deletes the backend half, so no report ever
    comes back — the handler itself must trigger the trace export."""
    from aiohttp.test_utils import TestClient, TestServer

    from finetune_controller_tpu.controller.config import Settings
    from finetune_controller_tpu.controller.objectstore import Presigner
    from finetune_controller_tpu.controller.runtime import Runtime
    from finetune_controller_tpu.controller.server import build_app

    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        settings = Settings(
            state_dir=str(tmp_path / "state"),
            object_store_root=str(tmp_path / "objects"),
        )
        runtime = Runtime(
            settings=settings, state=state, store=store, catalog=catalog,
            backend=backend, monitor=monitor,
            presigner=Presigner(settings.presign_secret),
        )
        app = build_app(runtime, with_monitor=False)
        client = TestClient(TestServer(app))
        await client.start_server()
        await _submit(state, store, backend, catalog, user_id="dev-user")

        r = await client.post("/api/v1/jobs/o-1/cancel")
        assert r.status == 200, await r.text()
        job = None
        for _ in range(100):
            job = await state.get_job("o-1")
            if job.metadata.get("trace_exported"):
                break
            await asyncio.sleep(0.05)
        assert job.metadata.get("trace_exported") is True
        trace = json.loads(
            await store.get_bytes(f"{job.artifacts_uri}/trace/trace.json")
        )
        assert trace["problems"] == [], trace["problems"]
        cancelled = [e for e in job.events if e["event"] == "cancelled"]
        # fixed idempotency key: racing cancel requests fold into one event
        assert [e.get("key") for e in cancelled] == ["cancelled"]
        await client.close()

    run(main())


# ---------------------------------------------------------------------------
# THE hard-path e2e (ISSUE 9 acceptance): preempt -> resize -> retry ->
# promote, every transition exactly once, in order, monotonic; the span
# tree gap-free with valid nesting.
# ---------------------------------------------------------------------------


def test_timeline_complete_across_preempt_resize_retry_promote(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from finetune_controller_tpu.controller.config import Settings
    from finetune_controller_tpu.controller.objectstore import Presigner
    from finetune_controller_tpu.controller.runtime import Runtime
    from finetune_controller_tpu.controller.server import build_app

    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock, obs=ObsHub()
        )
        settings = Settings(
            state_dir=str(tmp_path / "state"),
            object_store_root=str(tmp_path / "objects"),
            rate_limit_promote_per_min=1000,
        )
        runtime = Runtime(
            settings=settings, state=state, store=store, catalog=catalog,
            backend=backend, monitor=monitor,
            presigner=Presigner(settings.presign_secret),
        )
        app = build_app(runtime, with_monitor=False)
        client = TestClient(TestServer(app))
        await client.start_server()

        await _submit(state, store, backend, catalog, user_id="dev-user")

        def report(state_, **meta):
            kw = {}
            if state_ is BackendJobState.RUNNING:
                kw["start_time"] = clock.t
            if state_ is BackendJobState.SUCCEEDED:
                kw["start_time"], kw["completion_time"] = clock.t - 5, clock.t
            backend.reports["o-1"] = BackendJobReport(
                job_id="o-1", state=state_, metadata=meta, **kw
            )

        # attempt 1 runs, then is PREEMPTED (SIGTERM -> 143)
        report(BackendJobState.RUNNING)
        await monitor.tick()
        report(
            BackendJobState.FAILED, exit_code=143,
            preempted=True, preempted_by="job-hi",
        )
        await monitor.tick()
        assert (await state.get_job("o-1")).status is DatabaseStatus.RETRYING
        clock.advance(10)
        await monitor.tick()  # backoff expired -> resubmitted

        # attempt 2 runs, then a scheduler RESIZE (shrink to 1 slice)
        report(BackendJobState.RUNNING)
        await monitor.tick()
        report(
            BackendJobState.FAILED, exit_code=143,
            resize_to_num_slices=1, resize_kind="shrink",
        )
        await monitor.tick()
        clock.advance(10)
        await monitor.tick()

        # attempt 3 runs to completion
        report(BackendJobState.RUNNING)
        await monitor.tick()
        report(BackendJobState.SUCCEEDED)
        await monitor.tick()
        job = await state.get_job("o-1")
        assert job.status is DatabaseStatus.SUCCEEDED

        # promote through the real HTTP handler (promotion-started) and the
        # real background task (promoted)
        await store.put_bytes(
            f"{job.artifacts_uri}/checkpoints/step_2/state.msgpack", b"w"
        )
        r = await client.post("/api/v1/jobs/o-1/promote")
        assert r.status == 202, await r.text()
        for _ in range(100):
            job = await state.get_job("o-1")
            if job.promotion_status.value == "completed":
                break
            await asyncio.sleep(0.05)
        assert job.promotion_status.value == "completed"

        # --- the completeness assertions -------------------------------
        events = job.events
        names = [e["event"] for e in events]
        assert names == [
            "submitted",
            "running",
            "preempted", "retrying", "resubmitted",
            "running",
            "resized", "retrying", "resubmitted",
            "running",
            "succeeded",
            "promotion-started", "promoted",
        ]
        # exactly once: every keyed transition instance is unique
        keys = [e["key"] for e in events if "key" in e]
        assert len(keys) == len(set(keys))
        # in order, monotonic timestamps
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)
        # attempts attributed: the three running events are attempts 1..3
        runs = [e for e in events if e["event"] == "running"]
        assert [e["attrs"]["attempt"] for e in runs] == [1, 2, 3]
        # ONE numbering across planes: the supervisor's retrying events name
        # the dispatch that just ended and resubmitted names the next one —
        # the same scheme as running/FTC_ATTEMPT/trainer spans (a resize is
        # budget-exempt but still a dispatch)
        retries = [e for e in events if e["event"] == "retrying"]
        assert [e["attrs"]["attempt"] for e in retries] == [1, 2]
        resubs = [e for e in events if e["event"] == "resubmitted"]
        assert [e["attrs"]["attempt"] for e in resubs] == [2, 3]
        resized = next(e for e in events if e["event"] == "resized")
        assert resized["attrs"]["to_slices"] == 1
        assert resized["attrs"]["kind"] == "shrink"
        preempted = next(e for e in events if e["event"] == "preempted")
        assert preempted["attrs"]["by"] == "job-hi"

        # latency histograms observed along the way
        assert monitor.obs.queue_wait_seconds.count() == 3
        assert sup.obs.retry_latency_seconds.count() == 2

        # --- the gap-free span tree (acceptance criterion) -------------
        r = await client.get("/api/v1/jobs/o-1/trace")
        assert r.status == 200
        trace = await r.json()
        assert trace["trace_id"] == job.metadata["trace_id"]
        assert trace["problems"] == [], trace["problems"]
        names = {s["name"] for s in trace["spans"]}
        assert {
            "job", "pending", "attempt-1", "attempt-2", "attempt-3",
            "promotion",
        } <= names
        # parent/child nesting is valid and every lifecycle event is
        # covered by a span — re-check through the validator directly
        assert validate_trace(trace["spans"], job.events) == []

        # the timeline API serves the same events, oldest first
        r = await client.get("/api/v1/jobs/o-1/timeline")
        assert r.status == 200
        body = await r.json()
        assert [e["event"] for e in body["events"]] \
            == [e["event"] for e in job.events]
        assert body["trace_id"] == job.metadata["trace_id"]

        # the exported trace artifact landed next to the checkpoints
        assert await store.exists(f"{job.artifacts_uri}/trace/trace.json")

        await client.close()

    run(main())


# ---------------------------------------------------------------------------
# HTTP surface: profile guards, admin progress
# ---------------------------------------------------------------------------


def test_profile_endpoint_guards(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from finetune_controller_tpu.controller.config import Settings
    from finetune_controller_tpu.controller.objectstore import Presigner
    from finetune_controller_tpu.controller.runtime import Runtime
    from finetune_controller_tpu.controller.server import build_app

    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        settings = Settings(
            state_dir=str(tmp_path / "state"),
            object_store_root=str(tmp_path / "objects"),
        )
        runtime = Runtime(
            settings=settings, state=state, store=store, catalog=catalog,
            backend=backend, monitor=monitor,
            presigner=Presigner(settings.presign_secret),
        )
        client = TestClient(TestServer(build_app(runtime, with_monitor=False)))
        await client.start_server()
        await _submit(state, store, backend, catalog, user_id="dev-user")

        # not running -> 409
        r = await client.post("/api/v1/jobs/o-1/profile", json={"steps": 3})
        assert r.status == 409
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        # bad steps -> 400
        r = await client.post("/api/v1/jobs/o-1/profile", json={"steps": 0})
        assert r.status == 400
        # ScriptedBackend cannot deliver control files -> 501
        r = await client.post("/api/v1/jobs/o-1/profile", json={"steps": 3})
        assert r.status == 501
        # the ftc-ctl command routes through the same endpoint and
        # surfaces the server's refusal as an ApiError
        from finetune_controller_tpu.controller import ctl

        api = f"http://{client.server.host}:{client.server.port}"
        with pytest.raises(ctl.ApiError, match="cannot deliver"):
            await ctl.amain(ctl.build_parser().parse_args(
                ["--api", api, "profile", "o-1", "--steps", "3"]
            ))
        await client.close()

    run(main())


def test_admin_resilience_shows_progress_rate(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from finetune_controller_tpu.controller.config import Settings
    from finetune_controller_tpu.controller.objectstore import Presigner
    from finetune_controller_tpu.controller.runtime import Runtime
    from finetune_controller_tpu.controller.server import build_app
    from finetune_controller_tpu.resilience.heartbeat import (
        HEARTBEAT_FILENAME,
    )

    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        settings = Settings(
            state_dir=str(tmp_path / "state"),
            object_store_root=str(tmp_path / "objects"),
        )
        runtime = Runtime(
            settings=settings, state=state, store=store, catalog=catalog,
            backend=backend, monitor=monitor,
            presigner=Presigner(settings.presign_secret),
        )
        client = TestClient(TestServer(build_app(runtime, with_monitor=False)))
        await client.start_server()
        await _submit(state, store, backend, catalog, user_id="dev-user")
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        job = await state.get_job("o-1")
        await store.put_bytes(
            f"{job.artifacts_uri}/{HEARTBEAT_FILENAME}",
            json.dumps({
                "step": 120, "last_step": 120, "last_step_ms": 250.0,
                "ts": time.time(), "wall_time_s": 30.0, "pid": 1,
            }).encode(),
        )
        r = await client.get("/api/v1/admin/resilience")
        assert r.status == 200
        body = await r.json()
        rows = {p["job_id"]: p for p in body["progress"]}
        assert rows["o-1"]["last_step"] == 120
        assert rows["o-1"]["last_step_ms"] == 250.0
        assert rows["o-1"]["steps_per_min"] == pytest.approx(240.0)
        assert rows["o-1"]["heartbeat_age_s"] < 10
        await client.close()

    run(main())


def test_monitor_lease_kill_logs_last_known_step(tmp_path):
    """Satellite: LeaseChecker remembers the last heartbeat it parsed and
    the lease-killed timeline event names the step the job stalled at."""
    from finetune_controller_tpu.resilience.heartbeat import (
        HEARTBEAT_FILENAME,
        LeaseChecker,
    )

    async def main():
        clock = FakeClock()
        state, store, backend, catalog, sup, monitor = await _plane(
            tmp_path, clock=clock
        )
        monitor.lease = LeaseChecker(store, lease_s=5.0)
        await _submit(state, store, backend, catalog)
        await state.update_job_status("o-1", DatabaseStatus.RUNNING)
        job = await state.get_job("o-1")
        stale_ts = time.time() - 3600
        await store.put_bytes(
            f"{job.artifacts_uri}/{HEARTBEAT_FILENAME}",
            json.dumps({
                "step": 77, "last_step": 77, "last_step_ms": 120.0,
                "ts": stale_ts, "wall_time_s": 60.0, "pid": 1,
            }).encode(),
        )
        backend.reports["o-1"] = BackendJobReport(
            job_id="o-1", state=BackendJobState.RUNNING,
            start_time=stale_ts - 10,
        )
        await monitor.tick()
        job = await state.get_job("o-1")
        killed = [e for e in job.events if e["event"] == "lease-killed"]
        assert len(killed) == 1
        assert killed[0]["attrs"]["last_step"] == 77
        assert monitor.lease_kills == 1
        # routed through the supervisor like any infra failure
        assert job.status is DatabaseStatus.RETRYING

    run(main())


# ---------------------------------------------------------------------------
# backends: deliver_file (the artifact channel, reverse direction)
# ---------------------------------------------------------------------------


def test_local_backend_deliver_file_atomic_and_contained(tmp_path):
    from finetune_controller_tpu.controller.backends.base import BackendError
    from finetune_controller_tpu.controller.backends.local import (
        LocalProcessBackend,
        _JobHandle,
    )

    async def main():
        store = LocalObjectStore(tmp_path / "objects")
        backend = LocalProcessBackend(
            tmp_path / "sandboxes", store, _catalog()
        )
        sandbox = tmp_path / "sandboxes" / "d-1"
        handle = _JobHandle("d-1", sandbox, "artifacts/d-1", [])
        handle.artifacts_dir.mkdir(parents=True)
        backend._handles["d-1"] = handle

        assert await backend.deliver_file(
            "d-1", "profile_request.json", b'{"steps": 3}'
        )
        dest = handle.artifacts_dir / "profile_request.json"
        assert json.loads(dest.read_text()) == {"steps": 3}
        assert not dest.with_name(dest.name + ".tmp").exists()  # atomic

        # sandbox containment: a traversal path is refused loudly
        with pytest.raises(BackendError):
            await backend.deliver_file(
                "d-1", "../../outside.json", b"x"
            )
        assert not (tmp_path / "outside.json").exists()

        # unknown job: not delivered, not crashed
        assert not await backend.deliver_file("nope", "f.json", b"x")
        await backend.close()

    run(main())


# ---------------------------------------------------------------------------
# satellites: stream-logger attribution prefix, heartbeat progress fields
# ---------------------------------------------------------------------------


def test_stream_logger_prefixes_lines_with_trace_and_attempt():
    from finetune_controller_tpu.controller.stream_logger import (
        LogStreamManager,
    )

    class _Job:
        metadata = {
            "trace_id": "abcdef0123456789" * 2,
            "attempt_history": [{"attempt": 1}],
        }

    mgr = LogStreamManager.__new__(LogStreamManager)
    mgr._gate_open = True
    mgr._prefix = ""
    mgr.search_string = ""
    mgr._set_prefix(_Job())
    assert mgr._filter("loss 0.5") == "[abcdef01#a2] loss 0.5"
    # jobs from before the observability layer stream unprefixed
    mgr2 = LogStreamManager.__new__(LogStreamManager)
    mgr2._gate_open = True
    mgr2._prefix = ""
    mgr2.search_string = ""

    class _Legacy:
        metadata = {}

    mgr2._set_prefix(_Legacy())
    assert mgr2._filter("plain line") == "plain line"


def test_stream_logger_prefix_tracks_retry_attempts():
    """A follow stream attached during attempt 1 must label attempt 2's
    lines with #a2: the supervisor resubmits into the SAME log stream, so
    the prefix is re-resolved on the poll cadence, not frozen at start."""
    from finetune_controller_tpu.controller.stream_logger import (
        LogStreamManager,
    )

    class _Ws:
        closed = False

        def __init__(self):
            self.sent = []

        async def send_str(self, text):
            self.sent.append(text)

    class _Job:
        status = DatabaseStatus.RUNNING
        queue_position = None
        metadata = {
            "trace_id": "abcdef0123456789" * 2,
            "attempt_history": [],
        }

    class _State:
        async def get_job(self, job_id):
            return _Job()

    class _Backend:
        async def read_logs(self, job_id, follow=True, last_lines=None):
            async def gen():
                yield "attempt one line"
                # the retry lands: one more failure in the history
                _Job.metadata = {
                    **_Job.metadata,
                    "attempt_history": [{"attempt": 1}],
                }
                yield "attempt two line"

            return gen()

    ws = _Ws()
    mgr = LogStreamManager(
        ws, "j-1", _State(), _Backend(), follow=True, start_poll_s=0.0,
    )
    run(mgr.run())
    assert ws.sent == [
        "[abcdef01#a1] attempt one line",
        "[abcdef01#a2] attempt two line",
    ]


def test_stream_logger_prefix_refresh_stays_throttled_without_a_record():
    """A gone job record must not defeat the refresh throttle: the poll
    interval holds even when get_job keeps returning None (otherwise every
    streamed line costs a statestore query)."""
    from finetune_controller_tpu.controller.stream_logger import (
        LogStreamManager,
    )

    calls = []

    class _State:
        async def get_job(self, job_id):
            calls.append(job_id)
            return None

    mgr = LogStreamManager.__new__(LogStreamManager)
    mgr.job_id = "j-1"
    mgr.state = _State()
    mgr.start_poll_s = 60.0
    mgr._prefix = ""
    mgr._prefix_at = 0.0

    async def main():
        await mgr._refresh_prefix()  # first call: throttle expired, queries
        await mgr._refresh_prefix()  # immediately after: throttled
        await mgr._refresh_prefix()

    run(main())
    assert calls == ["j-1"]


def test_warm_spawn_scrubs_trace_env(tmp_path, monkeypatch):
    """The warm pool is replenished with the finished job's env: the dead
    job's FTC_TRACE_ID/FTC_ATTEMPT must not ride into a pooled worker (the
    next claimant injects its own identity via the request line)."""
    from finetune_controller_tpu.controller.backends.local import (
        LocalProcessBackend,
    )

    async def main():
        store = LocalObjectStore(tmp_path / "objects")
        backend = LocalProcessBackend(
            tmp_path / "sandboxes", store, _catalog(), warm_workers=1,
        )
        captured = {}

        async def fake_exec(*cmd, env=None, **kwargs):
            captured["env"] = env

            class _Proc:
                returncode = None
                pid = 4242

            return _Proc()

        monkeypatch.setattr(asyncio, "create_subprocess_exec", fake_exec)
        await backend._spawn_warm({
            "JAX_PLATFORMS": "cpu",
            "FTC_TRACE_ID": "d" * 32,
            "FTC_ATTEMPT": "3",
        })
        env = captured["env"]
        assert "FTC_TRACE_ID" not in env and "FTC_ATTEMPT" not in env
        assert env["JAX_PLATFORMS"] == "cpu"  # runtime env is preserved

    run(main())


def test_heartbeat_carries_progress_fields(tmp_path):
    from finetune_controller_tpu.resilience.heartbeat import (
        HeartbeatWriter,
        parse_heartbeat,
    )

    w = HeartbeatWriter(str(tmp_path), interval_s=0.0)
    assert w.beat(42, step_ms=123.4567)
    hb = parse_heartbeat((tmp_path / "heartbeat.json").read_bytes())
    assert hb["last_step"] == 42
    assert hb["step"] == 42  # the PR-3 field stays for old readers
    assert hb["last_step_ms"] == 123.457
    # step_ms is optional — the eval-loop beats don't carry one
    assert w.beat(43, force=True)
    hb = parse_heartbeat((tmp_path / "heartbeat.json").read_bytes())
    assert hb["last_step"] == 43
    assert "last_step_ms" not in hb
