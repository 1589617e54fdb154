"""Headline benchmark: LoRA SFT training throughput, tokens/sec/chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N, ...}

The reference (`acceleratedscience/finetune-controller`) publishes **no**
performance numbers (BASELINE.json: "published": {}) — it is a k8s control
plane whose training throughput belongs to user containers.  The baseline is
therefore self-established: ``vs_baseline`` is measured throughput divided by
a roofline-derived target for the benchmark hardware (40% MFU on the model's
6*N FLOPs/token), so >1.0 means we beat the target, and the number stays
comparable across rounds.

This default mode (and BENCH_MODE=qlora|mm|moe) measures a chip: with no TPU
it fails — there is no CPU leg, no cached verdict and no borrowed number —
and a ``device_kind`` with no published peak is an error.  The other modes
(BENCH_MODE=obs|chaos|sched|dpo, and serve off the chip) are functional
gates on scale-free ratios of the tiny config; they default to the CPU and
what they print is never a chip measurement.

Measurement discipline (round-2 rework):
  * the timed window is bounded by ``jax.block_until_ready`` on the FULL
    final state (not just a loss scalar), so async dispatch / lazy runtimes
    cannot make steps appear free — every step's device work must complete
    inside the window.  Steps are NOT individually blocked: per-step blocking
    would serialize host dispatch against the device and undercount the
    host/device overlap real training gets (measured ~87 ms/step on the
    TinyLlama config).  Per-step spread is still reported from a separate
    individually-blocked probe window so stragglers stay visible;
  * achieved MFU is computed and the bench REFUSES to print a number when
    MFU > 1.0 — an impossible figure is a measurement bug, not a result;
  * the timed window's losses must be finite and must not regress above the
    warmup loss (the step must be doing real optimization work);
  * throughput is the timed window's token count over its wall time; the
    probe window's p10/p90 per-step times are reported alongside.

Env knobs: BENCH_PRESET, BENCH_STEPS, BENCH_BATCH, BENCH_SEQ, BENCH_TINY=1
(the tiny presets, still on the chip), BENCH_MODE=qlora (int4 config #3),
BENCH_REMAT_POLICY, BENCH_ATTN_IMPL, BENCH_FROZEN_DTYPE, BENCH_LOGITS_DTYPE (perf experiments),
BENCH_RECOMPILE_BUDGET (distinct jit signatures allowed before the run is
declared a measurement bug and aborted — analysis/recompile_guard.py; 0 off),
BENCH_TRANSFER_GUARD (default on: the trainer step and serve decode hot
windows run under FTC_TRANSFER_GUARD=raise — analysis/transfer_guard.py — so
a reintroduced device<->host sync ABORTS the timed window; 0 disables).

Input-pipeline knobs (round 6): BENCH_PREFETCH (background prefetch depth
for the batch stream, default 2; 0 = synchronous host build on the timing
thread) and BENCH_PREFETCH_AB (default on in BENCH_MODE=mm: run a prefetch
off/on A/B over REAL decoded images — a generated on-disk jsonl of PNGs fed
through data/mm_loader.py with the pixel cache disabled — and attach the
per-leg step time + input_fraction under "prefetch_ab"). Every bench JSON now
carries "input_fraction": the share of the timed window the training thread
spent WAITING on its next batch — the number that catches an input-bound
config that raw tokens/sec would hide.

Serving knobs (BENCH_MODE=serve): BENCH_SERVE_REQUESTS, BENCH_SERVE_NEW_TOKENS,
BENCH_SERVE_SLOTS, and — for the prefix-reuse A/B (ISSUE 6, gated) —
BENCH_SERVE_PREFIX_LEN (shared system-prompt length, default 240) and
BENCH_SERVE_PREFIX_CACHE_MB (snapshot budget, default 64).  Paged-KV +
multi-tenant gates (ISSUE 11): BENCH_SERVE_PAGED (1 = run the paged A/B;
default on), BENCH_SERVE_PAGE_TOKENS (page size, default 16) and
BENCH_SERVE_ADAPTERS (multiplexed tenants, default 4) — gated on >= 2x
concurrent lanes at a fixed KV byte budget, >= 0.9x mixed-workload tok/s at
equal concurrency (bit-identical outputs), and multiplexed-vs-dedicated
bit-identity across adapters.  Cross-process transport gates (ISSUE 12):
BENCH_SERVE_TRANSPORT (1 = run the process-mode A/B; default on),
BENCH_SERVE_CONC (concurrent mixed-length requests, floor 64),
BENCH_SERVE_TRANSPORT_WORKERS / _SLOTS — gated (multi-core hosts) on
process-mode N-worker throughput >= 1.5x one worker, beating the
in-process contention baseline, and the 64+-concurrent p95 latency
fair-share bound.

Observability knobs (BENCH_MODE=obs, gated <2% overhead): BENCH_OBS_STEPS,
BENCH_OBS_ROUNDS, BENCH_BATCH, BENCH_SEQ (docs/observability.md).
"""

from __future__ import annotations

import json
import os
import sys
import time


# Peak bf16 TFLOP/s per chip, by jax device_kind substring (public specs).
# A device that is not in this table is an error, never a default.
PEAK_TFLOPS = [
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]
TARGET_MFU = 0.40


def _jsonable(x):
    """Make a diagnostic value RFC-JSON safe (NaN/Inf become strings)."""
    import math

    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    return x


def fail(reason: str, **diag) -> None:
    """Refuse to emit a benchmark number; print a diagnostic and exit 1."""
    safe = {k: _jsonable(v) for k, v in diag.items()}
    print(json.dumps({"bench_error": reason, **safe}), file=sys.stderr)
    sys.exit(1)


def _peak_tflops(device_kind: str) -> float:
    """Published bf16 peak of this device kind; an unknown kind fails the
    bench rather than borrowing another chip's peak."""
    kind = device_kind.lower()
    for key, tflops in PEAK_TFLOPS:
        if key in kind:
            return tflops
    fail("unknown device_kind: no published peak to compute MFU against",
         device_kind=device_kind, known=[k for k, _ in PEAK_TFLOPS])


# Raw-measurement log: every chip-measured bench number is appended here.
# Nothing reads it back; the 2026-07-31 lines in the committed file are
# history from older code (BASELINE.md).
SESSION_LOG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tpu_session.jsonl")


def _session_log_append(record: dict) -> None:
    """Append a real-TPU measurement to the committed session log.

    Every chip-measured bench number must exist as a raw record, however the
    bench was invoked — numbers living only in prose have no provenance.
    Disable with BENCH_SESSION_LOG=0.
    """
    from finetune_controller_tpu.platform import env_flag

    if not env_flag("BENCH_SESSION_LOG", default=True):
        return
    env = {k: v for k, v in os.environ.items()
           if k.startswith(("BENCH_", "FTC_")) and k != "BENCH_SESSION_LOG"}
    rec = {"ts": round(time.time(), 1), "step": "adhoc_bench", "env": env,
           **record}
    try:
        with open(SESSION_LOG, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as e:
        print(f"session-log append failed: {e}", file=sys.stderr)


def _write_mm_bench_dataset(dir_path: str, n_rows: int, src_px: int) -> str:
    """Write an image-bearing jsonl of REAL encoded images (PNG via PIL when
    available, ``.npy`` otherwise) so the mm input A/B measures genuine
    per-batch decode+resize host work, not synthetic in-memory arrays."""
    import numpy as np

    rng = np.random.default_rng(0)
    path = os.path.join(dir_path, "mm_bench.jsonl")
    with open(path, "w") as f:
        for i in range(n_rows):
            arr = rng.integers(0, 256, (src_px, src_px, 3)).astype("uint8")
            try:
                from PIL import Image

                name = f"img_{i:03d}.png"
                Image.fromarray(arr).save(os.path.join(dir_path, name))
            except ImportError:
                name = f"img_{i:03d}.npy"
                np.save(os.path.join(dir_path, name), arr)
            f.write(json.dumps({
                "image": name,
                "prompt": f"describe image {i}: ",
                "completion": "a square of colored noise",
            }) + "\n")
    return path


def measure_mm_prefetch_ab(
    trainer, state, dataset_path: str, *,
    image_size: int, batch: int, seq: int,
    steps: int = 8, depth: int = 2, warmup: int = 2,
):
    """Prefetch off/on A/B over the real multimodal loader (pixel cache
    disabled, so every batch pays its decode+resize — the steady-state cost
    of any epoch past the cache cap).

    Steps are individually blocked so each leg's step time is deterministic;
    the device wait releases the GIL, which is exactly the window the
    prefetch producer uses to build (and device_put) the next batch.
    Per-leg step time is the MEDIAN over the timed steps (host-side decode
    timing on a shared box is long-tailed; a mean would let one scheduler
    hiccup decide the A/B), while input_fraction keeps the honest totals.
    Returns ``(state, legs)`` where legs carries per-leg step time,
    input wait, and input_fraction, plus the off/on speedup.
    """
    import jax
    import numpy as np

    from finetune_controller_tpu.data.mm_loader import mm_jsonl_batches
    from finetune_controller_tpu.data.prefetch import prefetch_batches

    legs: dict = {}
    for leg, leg_depth in (("off", 0), ("on", depth)):
        raw = mm_jsonl_batches(
            dataset_path, batch_size=batch, seq_len=seq,
            image_size=image_size, pixel_cache_size=0,
        )
        it = prefetch_batches(
            raw, depth=leg_depth,
            transfer=trainer.shard_batch if leg_depth else None,
        )
        try:
            for _ in range(warmup):
                state, _ = trainer.step(state, next(it))
                state = jax.block_until_ready(state)
            input_s = 0.0
            step_times = []
            t0 = time.perf_counter()
            for _ in range(steps):
                ts = time.perf_counter()
                b = next(it)
                input_s += time.perf_counter() - ts
                state, _ = trainer.step(state, b)
                state = jax.block_until_ready(state)
                step_times.append(time.perf_counter() - ts)
            total_s = time.perf_counter() - t0
        finally:
            if hasattr(it, "close"):
                it.close()
        legs[leg] = {
            "step_time_avg_s": round(float(np.median(step_times)), 4),
            "input_ms_avg": round(input_s / steps * 1000, 2),
            "input_fraction": round(input_s / total_s, 4),
        }
    legs["speedup"] = round(
        legs["off"]["step_time_avg_s"]
        / max(legs["on"]["step_time_avg_s"], 1e-9), 3,
    )
    return state, legs


def _measure_obs() -> dict:
    """BENCH_MODE=obs: the tracing-overhead gate (docs/observability.md).

    Runs the SAME tiny fit repeatedly over identical synthetic batches,
    alternating the obs layer off (``FTC_TRACE=0``) and on within each
    round — the phase clock, event log, span recorder, AND the
    histogram-observation path the monitor runs on every synced row (fed
    here through ``on_metrics``).  The gate: the FASTEST window step time
    with tracing on must stay within 2% of tracing off — external load
    only ever ADDS time, so the two floors compare the true per-step cost
    while means/medians would gate on the box's noise (a whole leg landing
    in a slow phase shifts every mid-distribution statistic).  Rounds
    alternate on/off order to cancel slow drift; one untimed warmup fit
    pays the jit compile for both legs (the trainer instance — and so the
    jit cache — is shared).

    Knobs: BENCH_OBS_STEPS (per leg, default 30), BENCH_OBS_ROUNDS
    (default 8), BENCH_BATCH, BENCH_SEQ.  Legs are SHORT and alternated so
    both arms sample every phase of the box's seconds-scale load drift —
    one long leg per arm lets a busy phase land entirely on one side.
    """
    import gc
    import shutil
    import tempfile

    import jax
    import numpy as np

    from finetune_controller_tpu.data.synthetic import synthetic_batches
    from finetune_controller_tpu.models.llama import PRESETS
    from finetune_controller_tpu.models.lora import LoRAConfig
    from finetune_controller_tpu.obs.prom import ObsHub
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    preset = os.environ.get("BENCH_PRESET", "tiny-test")
    steps = int(os.environ.get("BENCH_OBS_STEPS", "30"))
    rounds = int(os.environ.get("BENCH_OBS_ROUNDS", "8"))
    # steps sized to tens of ms: the obs layer's per-step cost is FIXED
    # (a few perf_counter calls + a throttled stat), so measuring against
    # a representative step length is both honest — real jobs' steps are
    # far longer than tiny-test's 3ms — and resolvable on a noisy shared
    # box, where scheduler jitter swamps a 2% effect at small steps
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "128"))

    model_cfg = PRESETS[preset].replace(lora=LoRAConfig(rank=4))
    train_cfg = TrainConfig(
        mode="lora", learning_rate=1e-3, warmup_steps=2, total_steps=steps,
        batch_size=batch, seq_len=seq, log_every=10, checkpoint_every=10**9,
        prefetch=0, heartbeat_interval_s=0,
    )
    trainer = Trainer(model_cfg, train_cfg)
    hub = ObsHub()

    tokens_per_batch = batch * seq

    def leg(trace_on: bool) -> list:
        """One fit; returns the PER-WINDOW mean step seconds derived from
        each logged row's ``tokens_per_sec`` — measured inside the step
        loop, so the final blocking save and state init stay out of the
        sample, and a load spike poisons one window, not the whole leg.
        The on-leg also pays the monitor-side histogram observation per
        logged row, exactly like a live monitor would."""
        os.environ["FTC_TRACE"] = "1" if trace_on else "0"
        if trace_on:
            os.environ["FTC_TRACE_ID"] = "b" * 32
        windows: list = []

        def on_metrics(step, m):
            windows.append(tokens_per_batch / max(m["tokens_per_sec"], 1e-9))
            if trace_on:
                hub.observe_step_phases(m)

        d = tempfile.mkdtemp(prefix="ftc_obs_bench_")
        # even the GC slate between legs, then keep the collector out of
        # the timed windows: a cycle collection landing mid-window is
        # millisecond noise that hits whichever arm happens to cross the
        # allocation threshold — the allocations themselves (the real,
        # recurring cost of the obs layer) are still fully timed
        gc.collect()
        gc.disable()
        try:
            batches = synthetic_batches(
                batch, seq, model_cfg.vocab_size, task="increment"
            )
            trainer.fit(batches, d, resume=False, on_metrics=on_metrics)
            return windows
        finally:
            gc.enable()
            shutil.rmtree(d, ignore_errors=True)

    def measure() -> tuple:
        offs, ons = [], []
        for i in range(rounds):
            order = (False, True) if i % 2 == 0 else (True, False)
            for trace_on in order:
                (ons if trace_on else offs).extend(leg(trace_on))
        off_floor = float(np.min(offs))
        on_floor = float(np.min(ons))
        pct = (on_floor / max(off_floor, 1e-12) - 1.0) * 100.0
        return pct, off_floor, on_floor, len(offs)

    saved = {k: os.environ.get(k) for k in ("FTC_TRACE", "FTC_TRACE_ID")}
    attempts = []
    try:
        leg(False)  # untimed warmup: jit compile + state init caches
        # noise on a shared box only INFLATES a measurement, never deflates
        # it — so any attempt under the gate proves the true overhead is
        # under it, and best-of-3 keeps a load spike from failing the gate
        for _ in range(3):
            result = measure()
            attempts.append(round(result[0], 3))
            if result[0] < 2.0:
                break
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    overhead_pct, off_floor, on_floor, n_windows = result
    if overhead_pct >= 2.0:
        fail(
            "obs bench: tracing overhead breached the 2% step-time gate "
            "on all attempts",
            attempts=attempts,
            step_time_off_ms=round(off_floor * 1000, 4),
            step_time_on_ms=round(on_floor * 1000, 4),
            windows=n_windows,
        )
    if hub.step_phase_ms.count(phase="compute") == 0:
        fail("obs bench: the on-leg produced no phase histogram samples")
    return {
        "metric": f"obs_overhead_pct[{preset},bs{batch},seq{seq},"
                  f"steps{steps}x{rounds}]",
        "value": round(overhead_pct, 3),
        "unit": "% fastest window step time (tracing on vs FTC_TRACE=0)",
        "gate_pct": 2.0,
        "step_time_off_ms": round(off_floor * 1000, 4),
        "step_time_on_ms": round(on_floor * 1000, 4),
        "windows": n_windows,
        "attempts": attempts,
        "phase_samples": hub.step_phase_ms.count(phase="compute"),
        "device_kind": jax.devices()[0].device_kind,
    }


def _measure_chaos_recovery() -> dict:
    """BENCH_MODE=chaos: time the supervised-retry loop end to end.

    Runs a tiny job on the local backend, SIGTERM-kills it after its first
    committed checkpoint (backend restart budget zeroed so the CONTROLLER
    half — classify → backoff → resubmit-with-resume, docs/resilience.md —
    does the recovery), and reports the operator-facing latencies:

      detect_s    kill → the monitor classifies the failure (RETRYING)
      requeue_s   kill → the supervisor's resubmission hits the backend
      recover_s   kill → the respawned attempt reaches RUNNING
      total_s     submit → SUCCEEDED, both attempts included

    These are the production SLO numbers for a preemptible pool: how much
    wall clock one revocation costs beyond the backoff delay itself.
    """
    import asyncio
    import tempfile
    import time as _time
    from pathlib import Path

    from finetune_controller_tpu.controller.backends.local import LocalProcessBackend
    from finetune_controller_tpu.controller.examples import (
        LoRASFTArguments, TinyTestLoRA,
    )
    from finetune_controller_tpu.controller.monitor import JobMonitor
    from finetune_controller_tpu.controller.objectstore import LocalObjectStore
    from finetune_controller_tpu.controller.schemas import DatabaseStatus, JobInput
    from finetune_controller_tpu.controller.statestore import StateStore
    from finetune_controller_tpu.controller.task_builder import (
        DatasetInput, task_builder,
    )
    from finetune_controller_tpu.controller.devices import (
        DeviceCatalog, DeviceFlavor, FlavorQuota,
    )
    from finetune_controller_tpu.controller.registry import load_builtin_models
    from finetune_controller_tpu.resilience.policy import RetryPolicy
    from finetune_controller_tpu.resilience.supervisor import RetrySupervisor

    load_builtin_models()  # the supervisor rebuilds the spec from the registry

    steps = int(os.environ.get("BENCH_STEPS", "400"))
    ckpt_every = int(os.environ.get("BENCH_CKPT_EVERY", "50"))
    backoff_s = float(os.environ.get("BENCH_RETRY_BACKOFF", "0.2"))

    async def run(tmp: Path) -> dict:
        state = StateStore(tmp / "state")
        store = LocalObjectStore(tmp / "objects")
        catalog = DeviceCatalog(
            flavors=[DeviceFlavor(name="chip-1", generation="cpu", hosts=1,
                                  chips_per_host=1, runtime="cpu", queue="q")],
            quotas=[FlavorQuota(flavor="chip-1", nominal_chips=2)],
            default_flavor="chip-1",
        )
        backend = LocalProcessBackend(
            tmp / "sandboxes", store, catalog,
            sync_interval_s=0.2, backoff_limit=0,
        )
        supervisor = RetrySupervisor(
            state, backend, catalog,
            policy=RetryPolicy(max_attempts=3, base_delay_s=backoff_s,
                               max_delay_s=backoff_s, seed=0),
        )
        monitor = JobMonitor(state, store, backend, interval_s=0.1,
                             supervisor=supervisor)
        await state.connect()
        spec = TinyTestLoRA(training_arguments=LoRASFTArguments(
            total_steps=steps, warmup_steps=1, batch_size=2, seq_len=16,
            lora_rank=2, log_every=ckpt_every, checkpoint_every=ckpt_every,
        ))
        job = JobInput(job_id="chaos-bench-1", user_id="bench",
                       model_name="tiny-test-lora", device="chip-1",
                       arguments=spec.training_arguments.model_dump())
        t_submit = _time.perf_counter()
        await task_builder(
            job, spec, DatasetInput(),
            state=state, store=store, backend=backend, catalog=catalog,
            datasets_bucket="datasets", artifacts_bucket="artifacts",
        )
        import re as _re

        handle = backend._handles["chaos-bench-1"]
        ckpt_dir = handle.artifacts_dir / "checkpoints"
        deadline = _time.monotonic() + 300
        committed = _re.compile(r"^step_\d+$")  # NOT in-flight *-tmp staging

        def has_committed() -> bool:
            return ckpt_dir.is_dir() and any(
                committed.match(p.name) for p in ckpt_dir.iterdir()
            )

        while not has_committed():
            if _time.monotonic() > deadline:
                fail("chaos bench: no checkpoint appeared within 300s")
            await asyncio.sleep(0.1)
        assert await backend.inject_fault("chaos-bench-1", signum=15)
        t_kill = _time.perf_counter()
        t_detect = t_requeue = t_recover = None
        while True:
            await monitor.tick()
            now = _time.perf_counter()
            rec = await state.get_job("chaos-bench-1")
            if t_detect is None and rec.status is DatabaseStatus.RETRYING:
                t_detect = now
            if t_requeue is None and supervisor.resubmits > 0:
                t_requeue = now
            report = await backend.get_job("chaos-bench-1")
            if (t_recover is None and t_requeue is not None
                    and report is not None and report.state.value == "Running"):
                t_recover = now
            if rec.status.is_final:
                break
            if _time.monotonic() > deadline:
                fail("chaos bench: job not final within 300s", status=str(rec.status))
            await asyncio.sleep(0.05)
        t_done = _time.perf_counter()
        attempts = rec.metadata.get("attempt_history") or []
        if rec.status is not DatabaseStatus.SUCCEEDED:
            fail("chaos bench: job did not recover to SUCCEEDED",
                 status=str(rec.status), attempts=attempts)
        if len(attempts) != 1:
            fail("chaos bench: expected exactly one recorded kill",
                 attempts=attempts)
        out = {
            "metric": f"chaos_recovery[tiny-test,steps{steps},ckpt{ckpt_every}]",
            "value": round(t_recover - t_kill, 3) if t_recover else None,
            "unit": "s (kill -> respawned attempt RUNNING)",
            "detect_s": round(t_detect - t_kill, 3) if t_detect else None,
            "requeue_s": round(t_requeue - t_kill, 3) if t_requeue else None,
            "recover_s": round(t_recover - t_kill, 3) if t_recover else None,
            "total_s": round(t_done - t_submit, 3),
            "backoff_s": backoff_s,
            "failure_class": attempts[0]["failure_class"],
            "restored_checkpoints": (await state.get_job("chaos-bench-1"))
                .metadata.get("restored_checkpoints"),
        }
        await backend.close()
        await state.close()
        return out

    with tempfile.TemporaryDirectory(prefix="ftc_chaos_bench_") as d:
        return asyncio.run(run(Path(d)))


def _measure_sched() -> dict:
    """BENCH_MODE=sched: fair-share vs FIFO, and resize vs full eviction.

    Two gated comparisons on the deterministic simulator (pure control
    flow: no accelerator, milliseconds):

    1. **fair-share vs FIFO** on the canonical head-of-line-blocking trace
       (PR 5): small-job p95 wait and the Jain index must both improve.
    2. **resize vs full eviction** on the capacity-reclaim trace
       (``sched/sim.py::elastic_trace`` — a whole-cluster XL job loses
       chips to a high-priority reclaim + tenant stream): resize must
       strictly reduce chip-seconds-of-progress-lost (checkpoint replay +
       exit-grace overhead + demanded-but-idle capacity), with Jain no
       worse and small-job p95 wait within two exit graces of the evict
       leg (ISSUE 7).

    Knobs: BENCH_SCHED_SEED, BENCH_SCHED_CHIPS, BENCH_SCHED_BIG,
    BENCH_SCHED_SMALL, BENCH_SCHED_GROW_DELAY (virtual seconds the grow
    pass waits for tenant-quiet before restoring a shrunk job).
    """
    from finetune_controller_tpu.controller.backends.scheduler import (
        GangScheduler,
    )
    from finetune_controller_tpu.sched import FairShareScheduler
    from finetune_controller_tpu.sched.sim import (
        TRACE_QUEUES,
        ClusterSim,
        elastic_trace,
        percentile,
        sim_catalog,
        synthetic_trace,
    )

    seed = int(os.environ.get("BENCH_SCHED_SEED", "0"))
    chips = int(os.environ.get("BENCH_SCHED_CHIPS", "8"))
    n_big = int(os.environ.get("BENCH_SCHED_BIG", "4"))
    n_small = int(os.environ.get("BENCH_SCHED_SMALL", "24"))
    grow_delay = float(os.environ.get("BENCH_SCHED_GROW_DELAY", "5"))
    preempt_exit_s = 1.0
    catalog = sim_catalog(chips)
    trace = synthetic_trace(seed, n_big=n_big, n_small=n_small)
    reclaim_trace = elastic_trace(seed)

    def leg(factory, trace) -> tuple[dict, "object"]:
        # both legs score fairness against the SAME entitlements
        report = ClusterSim(
            catalog, factory, queue_weights=TRACE_QUEUES,
            preempt_exit_s=preempt_exit_s,
        ).run(trace)
        unfinished = [
            o.job_id for o in report.outcomes.values() if o.finish_s is None
        ]
        if unfinished:
            fail("sched bench: jobs never finished", unfinished=unfinished)
        waits = report.waits(max_chips=1)
        lat = report.preempt_resume_latencies_s
        out = {
            "makespan_s": round(report.makespan_s, 1),
            "jain_fairness": round(report.jain_fairness, 3),
            "preemptions": report.preemptions,
            "resizes": report.resizes,
            "small_job_wait_p50_s": round(percentile(waits, 50), 1),
            "small_job_wait_p95_s": round(percentile(waits, 95), 1),
            "preempt_readmit_p50_s": (
                round(percentile(lat, 50), 1) if lat else None
            ),
            "preempt_readmit_p95_s": (
                round(percentile(lat, 95), 1) if lat else None
            ),
            "progress_lost_chip_s": round(
                report.progress_lost_chip_seconds, 1
            ),
            "replay_lost_chip_s": round(report.replay_lost_chip_seconds, 1),
            "exit_overhead_chip_s": round(
                report.exit_overhead_chip_seconds, 1
            ),
            "idle_demand_chip_s": round(report.idle_demand_chip_seconds, 1),
        }
        # gating uses the RAW report: an improvement smaller than the
        # display rounding grain must still count as an improvement
        return out, report

    def p95(report) -> float:
        return percentile(report.waits(max_chips=1), 95)

    # -- gate 1: fair-share vs FIFO (PR 5, unchanged) -----------------------
    fifo, fifo_r = leg(lambda clock: GangScheduler(catalog), trace)
    fair, fair_r = leg(
        lambda clock: FairShareScheduler(catalog, TRACE_QUEUES, clock=clock),
        trace,
    )
    if p95(fair_r) >= p95(fifo_r):
        fail(
            "sched bench: fair-share did not reduce small-job p95 wait",
            fifo=fifo, fairshare=fair,
        )
    if fair_r.jain_fairness <= fifo_r.jain_fairness:
        fail(
            "sched bench: fair-share did not improve the Jain index",
            fifo=fifo, fairshare=fair,
        )

    # -- gate 2: resize vs full eviction (ISSUE 7) --------------------------
    evict, evict_r = leg(
        lambda clock: FairShareScheduler(
            catalog, TRACE_QUEUES, clock=clock, resize=False,
        ),
        reclaim_trace,
    )
    resize, resize_r = leg(
        lambda clock: FairShareScheduler(
            catalog, TRACE_QUEUES, clock=clock,
            resize=True, grow_delay_s=grow_delay,
        ),
        reclaim_trace,
    )
    if (resize_r.progress_lost_chip_seconds
            >= evict_r.progress_lost_chip_seconds):
        fail(
            "sched bench: resize did not reduce chip-seconds of progress "
            "lost vs full eviction",
            evict=evict, resize=resize,
        )
    if resize_r.jain_fairness < evict_r.jain_fairness:
        fail(
            "sched bench: resize regressed Jain fairness vs eviction",
            evict=evict, resize=resize,
        )
    if p95(resize_r) > p95(evict_r) + 2.0 * preempt_exit_s + 0.5:
        # resize may pay up to two extra exit graces on the wait tail
        # (shrink cascades free chips in smaller pieces); more is a
        # regression
        fail(
            "sched bench: resize regressed small-job p95 wait vs eviction",
            evict=evict, resize=resize,
        )
    if resize_r.resizes <= 0:
        fail("sched bench: the resize leg never resized", resize=resize)

    return {
        "metric": (
            f"sched_progress_lost_chip_s[chips{chips},seed{seed},"
            f"grow{grow_delay:g}]"
        ),
        "value": resize["progress_lost_chip_s"],
        "unit": "chip-seconds of progress lost (resize, reclaim trace)",
        "fifo": fifo,
        "fairshare": fair,
        "fairshare_evict": evict,
        "fairshare_resize": resize,
        "wait_p95_speedup_vs_fifo": round(
            fifo["small_job_wait_p95_s"]
            / max(fair["small_job_wait_p95_s"], 1e-9), 1,
        ),
        "jain_delta_vs_fifo": round(
            fair["jain_fairness"] - fifo["jain_fairness"], 3
        ),
        "progress_lost_reduction": round(
            1.0 - resize_r.progress_lost_chip_seconds
            / max(evict_r.progress_lost_chip_seconds, 1e-9), 3,
        ),
        "jain_delta_resize_vs_evict": round(
            resize["jain_fairness"] - evict["jain_fairness"], 3
        ),
        "queues": TRACE_QUEUES,
    }


def _measure_dpo() -> dict:
    """BENCH_MODE=dpo: the preference-optimization gates (ISSUE 8).

    Gated legs on the tiny CPU-runnable config (the third is ISSUE 19's):

    1. **DPO** — train on the seeded synthetic preference set
       (``data/preference.py``): the reward margin must STRICTLY increase
       over the run (last-quarter mean > first-quarter mean) and final DPO
       accuracy on HELD-OUT pairs (disjoint seed region) must reach >= 0.7.
    2. **Actor/learner smoke** — the rlhf loop (``prefs/learner.py``) over
       two checkpoint commits: the actor must generate from checkpoint N,
       the learner commit N+1, and the actor reload N+1 within one rollout
       round — all inside the serve engine's existing compile budget (the
       armed RecompileGuard raises otherwise).
    3. **Disaggregated overlap** — one real remote rollout worker: its
       decode throughput while the learner steps concurrently must hold
       >= 0.9x its unloaded rate (records-only below 4 cores, per the
       ``gates_enforced`` convention).

    Knobs: BENCH_STEPS (DPO optimizer steps), BENCH_BATCH, BENCH_SEQ,
    BENCH_DPO_BETA, BENCH_DPO_EVAL_BATCHES, BENCH_DPO_OVERLAP_TOKENS.
    """
    import numpy as np

    import jax

    from finetune_controller_tpu.data.preference import (
        synthetic_preference_batches,
    )
    from finetune_controller_tpu.models.llama import PRESETS
    from finetune_controller_tpu.models.lora import LoRAConfig
    from finetune_controller_tpu.prefs.dpo_trainer import DPOTrainer
    from finetune_controller_tpu.train.trainer import TrainConfig

    preset = os.environ.get("BENCH_PRESET", "tiny-test")
    steps = int(os.environ.get("BENCH_STEPS", "80"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "32"))
    beta = float(os.environ.get("BENCH_DPO_BETA", "0.2"))
    eval_batches = int(os.environ.get("BENCH_DPO_EVAL_BATCHES", "8"))

    model_cfg = PRESETS[preset].replace(lora=LoRAConfig(rank=8))
    train_cfg = TrainConfig(
        task="dpo", dpo_beta=beta, batch_size=batch, seq_len=seq,
        total_steps=steps, warmup_steps=2, learning_rate=1e-3,
        eval_steps=eval_batches,
        log_every=10**9, checkpoint_every=10**9, prefetch=0,
        recompile_budget=int(os.environ.get("BENCH_RECOMPILE_BUDGET", "4")),
        recompile_action="raise",
    )
    trainer = DPOTrainer(model_cfg, train_cfg)
    state = trainer.init_state()
    batches = synthetic_preference_batches(
        batch, seq, model_cfg.vocab_size, seed=0
    )
    margins: list[float] = []
    pair_tput: list[float] = []
    for _ in range(steps):
        b = next(batches)
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, b)
        margins.append(float(metrics["reward_margin"]))  # syncs the device
        pair_tput.append(batch / (time.perf_counter() - t0))
    if not all(np.isfinite(margins)):
        fail("dpo bench: non-finite reward margin", margins=margins[:10])
    q = max(1, steps // 4)
    margin_first = float(np.mean(margins[:q]))
    margin_last = float(np.mean(margins[-q:]))
    if not margin_last > margin_first:
        fail(
            "dpo bench: reward margin did not increase over the run",
            margin_first=round(margin_first, 4),
            margin_last=round(margin_last, 4),
        )

    # held-out accuracy via the REAL eval path (disjoint seed region, the
    # train/cli.py offset) — the same evaluate() a dpo job's eval cadence runs
    held_out = synthetic_preference_batches(
        batch, seq, model_cfg.vocab_size, seed=100_003
    )
    heldout_acc = float(
        trainer.evaluate(state, held_out)["eval_dpo_accuracy"]
    )
    if heldout_acc < 0.7:
        fail(
            "dpo bench: held-out DPO accuracy below the 0.7 gate",
            heldout_accuracy=round(heldout_acc, 3),
        )

    # --- actor/learner smoke: generate from N, commit N+1, reload N+1 -----
    import csv
    import tempfile

    from finetune_controller_tpu.prefs.learner import (
        RolloutConfig, build_rlhf_loop,
    )

    ckpt_every = int(os.environ.get("BENCH_DPO_CKPT_EVERY", "5"))
    loop_cfg = TrainConfig(
        task="rlhf", dpo_beta=beta, batch_size=4, seq_len=seq,
        total_steps=3 * ckpt_every, warmup_steps=1, learning_rate=1e-3,
        log_every=ckpt_every, checkpoint_every=ckpt_every, prefetch=0,
        heartbeat_interval_s=0,
    )
    learner = DPOTrainer(model_cfg, loop_cfg)
    with tempfile.TemporaryDirectory(prefix="ftc_dpo_bench_") as d:
        stream, actor, buffer = build_rlhf_loop(
            learner, d,
            rollout=RolloutConfig(
                pairs_per_round=6, min_fill=6, buffer_capacity=64,
                max_new_tokens=8, slots=4, temperature=0.9,
            ),
        )
        learner.fit(stream, d, resume=True)
        with open(os.path.join(d, "metrics.csv"), newline="") as f:
            rows = list(csv.DictReader(f))
    versions = [int(float(r["actor_version"])) for r in rows]
    # the row logged at step k*ckpt_every trained on rollouts from the
    # checkpoint committed at (k-1)*ckpt_every: reload lag is exactly one
    # round
    expected = [max(0, int(float(r["step"])) - ckpt_every) for r in rows]
    if versions != expected:
        fail(
            "dpo bench: actor did not reload each committed checkpoint "
            "within one round",
            actor_versions=versions, expected=expected,
        )
    if actor.reloads < 2:
        fail("dpo bench: actor never cycled checkpoints",
             reloads=actor.reloads)
    if actor.compilations > actor.compile_budget:
        fail(  # the armed guard should have raised first
            "dpo bench: rollout engine exceeded its compile budget",
            compilations=actor.compilations, budget=actor.compile_budget,
        )
    loop_margins = [float(r["reward_margin"]) for r in rows]

    # --- disaggregated overlap leg (docs/preference.md §Disaggregated) ----
    # One REAL remote rollout worker; the gate: its decode throughput while
    # the learner steps concurrently must hold >= 0.9x its unloaded rate.
    # Enforced only with >= 4 cores (worker + learner need separate cores);
    # below that the numbers are recorded, not gated (`gates_enforced`).
    from finetune_controller_tpu.prefs.learner import (  # noqa: F811
        RolloutConfig as _RC,
    )
    from finetune_controller_tpu.prefs.rollout_plane import (
        build_remote_rlhf_loop,
    )

    overlap_enforced = (os.cpu_count() or 1) >= 4
    min_tokens = int(os.environ.get("BENCH_DPO_OVERLAP_TOKENS", "300"))
    overlap_cfg = TrainConfig(
        task="rlhf", dpo_beta=beta, batch_size=4, seq_len=seq,
        total_steps=10**9, warmup_steps=1, learning_rate=1e-3,
        log_every=10**9, checkpoint_every=10**9, prefetch=0,
        heartbeat_interval_s=0, rollout_workers=1,
    )
    ov_learner = DPOTrainer(model_cfg, overlap_cfg)
    with tempfile.TemporaryDirectory(prefix="ftc_dpo_overlap_") as d:
        stream, plane, _buf = build_remote_rlhf_loop(
            ov_learner, d,
            rollout=_RC(
                pairs_per_round=6, min_fill=6, buffer_capacity=256,
                max_new_tokens=8, slots=4, temperature=0.9,
            ),
            model_spec={"preset": preset, "lora": {"rank": 8}},
        )
        try:
            ov_state = ov_learner.init_state()
            b = next(stream)  # waits for the worker's first rounds
            ov_state, m = ov_learner.step(ov_state, b)
            float(m["reward_margin"])  # compile outside both windows

            def _decode_window(step_fn, timeout_s: float):
                # windowed decode rate from the worker's own cumulative
                # counters (tokens / seconds spent inside generate_pairs)
                s0 = plane.stats()
                k0 = s0["rollout_actor_tokens_generated"]
                deadline = time.monotonic() + timeout_s
                steps_done = 0
                while time.monotonic() < deadline:
                    st = plane.stats()
                    if st["rollout_actor_tokens_generated"] - k0 >= min_tokens:
                        break
                    if step_fn is not None:
                        step_fn()
                        steps_done += 1
                    else:
                        time.sleep(0.05)
                s1 = plane.stats()
                dtok = s1["rollout_actor_tokens_generated"] - k0
                dsec = (s1["rollout_actor_generate_seconds"]
                        - s0["rollout_actor_generate_seconds"])
                return dtok / max(dsec, 1e-9), dtok, steps_done

            rate_unloaded, tok_a, _ = _decode_window(None, 90.0)

            def _one_step():
                bb = next(stream)
                ov = ov_learner.step(_one_step.state, bb)
                _one_step.state = ov[0]
                float(ov[1]["reward_margin"])  # sync

            _one_step.state = ov_state
            rate_loaded, tok_b, learner_steps = _decode_window(
                _one_step, 180.0
            )
        finally:
            plane.close()
    overlap_ratio = rate_loaded / max(rate_unloaded, 1e-9)
    if overlap_enforced:
        if tok_a < min_tokens or tok_b < min_tokens:
            fail(
                "dpo bench: remote worker generated too few tokens to "
                "measure the overlap windows",
                unloaded_tokens=tok_a, loaded_tokens=tok_b,
                min_tokens=min_tokens,
            )
        if learner_steps < 2:
            fail(
                "dpo bench: learner made too few concurrent steps to prove "
                "overlap", learner_steps=learner_steps,
            )
        if overlap_ratio < 0.9:
            fail(
                "dpo bench: remote actor decode rate collapsed under "
                "concurrent learner steps",
                rate_unloaded=round(rate_unloaded, 1),
                rate_loaded=round(rate_loaded, 1),
                ratio=round(overlap_ratio, 3),
            )

    return {
        "metric": f"dpo_heldout_accuracy[{preset},bs{batch},seq{seq},"
                  f"steps{steps},beta{beta:g}]",
        "value": round(heldout_acc, 3),
        "unit": "held-out pair-ranking accuracy",
        "margin_first_quarter": round(margin_first, 4),
        "margin_last_quarter": round(margin_last, 4),
        "margin_gain": round(margin_last - margin_first, 4),
        "pairs_per_sec": round(float(np.median(pair_tput)), 1),
        "rlhf_smoke": {
            "actor_versions": versions,
            "reloads": actor.reloads,
            "bootstrap_pairs": actor.bootstrap_pairs,
            "rollout_pairs": actor.pairs_generated,
            "actor_tokens_per_sec": round(actor.tokens_per_sec, 1),
            "engine_compilations": actor.compilations,
            "engine_compile_budget": actor.compile_budget,
            "loop_margins": [round(m, 4) for m in loop_margins],
            "buffer_depth": buffer.depth,
        },
        "rollout_overlap": {
            "rate_unloaded_tok_s": round(rate_unloaded, 1),
            "rate_loaded_tok_s": round(rate_loaded, 1),
            "ratio": round(overlap_ratio, 3),
            "unloaded_tokens": tok_a,
            "loaded_tokens": tok_b,
            "learner_steps_concurrent": learner_steps,
            "gates_enforced": overlap_enforced,
            "cpu_count": os.cpu_count(),
        },
        "device_kind": jax.devices()[0].device_kind,
    }


def _measure_serve() -> dict:
    """BENCH_MODE=serve: continuous-batching engine vs sequential decode.

    The serving headline: aggregate tokens/s of ``serve.engine.BatchEngine``
    over N concurrent requests against the same requests run one at a time
    through ``cached_generate`` (the pre-serve path), plus per-request
    completion latency p50/p95 measured from a common start — the number a
    queued client actually experiences.  Both legs are warmed first (compiles
    excluded; steady-state serving is what is measured), and the engine's
    recompile guard is armed with on_excess="raise": a decode step compiling
    mid-window is a measurement bug, not a slow number.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from finetune_controller_tpu.models.generate import cached_generate
    from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
    from finetune_controller_tpu.models.lora import LoRAConfig
    from finetune_controller_tpu.serve.engine import (
        BatchEngine,
        EngineConfig,
        GenRequest,
    )

    from finetune_controller_tpu.platform import env_flag

    # transfer guard (analysis/transfer_guard.py): every engine this bench
    # builds — including process-mode workers, which inherit the env — runs
    # its decode dispatch under FTC_TRANSFER_GUARD=raise, so a reintroduced
    # device<->host sync ABORTS the timed window instead of deflating the
    # measured tok/s. BENCH_TRANSFER_GUARD=0 disables; an explicit
    # FTC_TRANSFER_GUARD in the env wins.
    transfer_guard_armed = env_flag("BENCH_TRANSFER_GUARD", default=True)
    if transfer_guard_armed:
        os.environ.setdefault("FTC_TRANSFER_GUARD", "raise")
    # shard audit (analysis/shard_audit.py): any serve-side model load this
    # bench (or its process-mode workers, which inherit the env) performs
    # asserts the rule-table shardings on the way in. An explicit
    # FTC_SHARD_AUDIT in the env wins; BENCH_SHARD_AUDIT=0 disables.
    if env_flag("BENCH_SHARD_AUDIT", default=True):
        os.environ.setdefault("FTC_SHARD_AUDIT", "raise")

    preset = os.environ.get("BENCH_PRESET", "tiny-test")
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "8"))
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", "32"))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", str(n_requests)))

    cfg = PRESETS[preset].replace(lora=LoRAConfig(rank=8))
    model = LlamaForCausalLM(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )
    rng = np.random.default_rng(0)
    # mixed prompt lengths across two buckets — the shape serving traffic has
    prompts = [
        list(rng.integers(1, cfg.vocab_size - 1, size=int(n)))
        for n in rng.integers(4, 24, size=n_requests)
    ]

    def reqs():
        return [
            GenRequest(request_id=f"r{i}", tokens=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)
        ]

    # --- sequential baseline: one request at a time through cached_generate
    def run_sequential() -> list[float]:
        done_at, t0 = [], time.perf_counter()
        for p in prompts:
            out = cached_generate(
                model, variables, jnp.asarray([p], jnp.int32),
                max_new_tokens=max_new,
            )
            jax.block_until_ready(out)
            done_at.append(time.perf_counter() - t0)
        return done_at

    run_sequential()  # warm: per-prompt-length decode fns compile here
    seq_done = run_sequential()
    seq_window = seq_done[-1]

    engine = BatchEngine(
        model, variables,
        EngineConfig(slots=slots, prompt_buckets=(32, 128),
                     max_new_tokens=max_new + 8),
    )
    engine.run(reqs())  # warm: fill buckets + the decode step compile here
    t0 = time.perf_counter()
    results = engine.run(reqs())
    engine_window = time.perf_counter() - t0
    # finished_at is monotonic-clock; re-zero against the earliest admission
    base = min(r.admitted_at for r in results.values())
    engine_done = sorted(r.finished_at - base for r in results.values())

    total_tokens = sum(len(r.generated) for r in results.values())
    if total_tokens != n_requests * max_new:
        fail(
            "serve bench generated an unexpected token count",
            total_tokens=total_tokens, expected=n_requests * max_new,
        )
    engine_tps = total_tokens / engine_window
    seq_tps = total_tokens / seq_window
    speedup = engine_tps / seq_tps

    def pct(xs: list[float], p: float) -> float:
        return float(np.percentile(np.asarray(xs), p))

    # --- prefix-reuse A/B (docs/serving.md): the ISSUE 6 gates ------------
    # (a) the EXISTING mixed workload must not regress with the cache on;
    # (b) a shared-system-prompt workload must cut time-to-first-token >= 2x
    #     and save > 50% of prefill tokens.
    cache_mb = int(os.environ.get("BENCH_SERVE_PREFIX_CACHE_MB", "64"))
    engine_on = BatchEngine(
        model, variables,
        EngineConfig(slots=slots, prompt_buckets=(32, 128),
                     max_new_tokens=max_new + 8,
                     prefix_cache_bytes=cache_mb << 20),
    )
    engine_on.run(reqs())  # warm pass 1: fill compiles + seeds the cache
    engine_on.run(reqs())  # warm pass 2: the hit path compiles fill_from
    t0 = time.perf_counter()
    results_on = engine_on.run(reqs())
    on_window = time.perf_counter() - t0
    for rid, r in results.items():
        if results_on[rid].generated != r.generated:
            fail("prefix cache changed greedy output on the mixed workload",
                 request_id=rid)
    mixed_on_tps = total_tokens / on_window
    if mixed_on_tps < 0.8 * engine_tps:
        # the cache must be ~free when it cannot help (same-run baseline =
        # the PR-4 configuration); 0.8 absorbs CPU timer noise on the tiny
        # preset — a real regression from trie/insert overhead is far larger
        fail(
            "prefix cache regressed the mixed serve workload",
            mixed_on_tps=round(mixed_on_tps, 1),
            mixed_off_tps=round(engine_tps, 1),
        )

    prefix_len = int(os.environ.get("BENCH_SERVE_PREFIX_LEN", "240"))
    suffix_len = 8
    pre_buckets = (32, prefix_len + 2 * suffix_len)
    system_prompt = list(
        rng.integers(1, cfg.vocab_size - 1, size=prefix_len)
    )
    shared_prompts = [
        system_prompt + list(
            rng.integers(1, cfg.vocab_size - 1, size=suffix_len)
        )
        for _ in range(n_requests)
    ]

    def shared_reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p,
                       max_new_tokens=max_new)
            for i, p in enumerate(shared_prompts)
        ]

    def ttft_and_drain(eng, requests):
        """Admit with per-request wall timing (TTFT: prefill + first token
        selection happen inside admit), then drain the batch."""
        ttfts, out, pending = [], {}, list(requests)
        while pending or eng.active_requests:
            while pending and eng.free_slots:
                r = pending.pop(0)
                t1 = time.perf_counter()
                done = eng.admit(r)
                ttfts.append(time.perf_counter() - t1)
                if done is not None:
                    out[r.request_id] = done
            for done in eng.step():
                out[done.request_id] = done
        return ttfts, out

    ab = {}
    for leg, cache_bytes in (("off", 0), ("on", cache_mb << 20)):
        eng = BatchEngine(
            model, variables,
            EngineConfig(slots=slots, prompt_buckets=pre_buckets,
                         max_new_tokens=max_new + 8,
                         prefix_cache_bytes=cache_bytes),
        )
        ttft_and_drain(eng, shared_reqs("w"))  # warm + seed the cache
        saved0 = eng.prefill_tokens_saved_total
        ttfts, out = ttft_and_drain(eng, shared_reqs("m"))
        ab[leg] = {
            "ttft_p50_s": round(pct(ttfts, 50), 5),
            "ttft_p95_s": round(pct(ttfts, 95), 5),
            "prefill_tokens_saved": eng.prefill_tokens_saved_total - saved0,
            "prefix_hits": eng.prefix_hits_total,
            "compilations": eng.compilations,
            "tokens": {r: out[r].generated for r in sorted(out)},
        }
    if ab["on"].pop("tokens") != ab["off"].pop("tokens"):
        fail("prefix cache changed greedy output on the shared-prefix "
             "workload")
    ttft_speedup = ab["off"]["ttft_p50_s"] / ab["on"]["ttft_p50_s"]
    if ttft_speedup < 2.0:
        fail(
            "shared-prefix TTFT improvement below the 2x gate",
            ttft_speedup=round(ttft_speedup, 2), **{
                f"ttft_{leg}_p50_s": ab[leg]["ttft_p50_s"]
                for leg in ("off", "on")
            },
        )
    prompt_tokens_total = sum(len(p) for p in shared_prompts)
    saved_fraction = ab["on"]["prefill_tokens_saved"] / prompt_tokens_total
    if saved_fraction <= 0.5:
        fail(
            "prefix cache saved <= 50% of prompt tokens on the "
            "shared-prefix workload",
            saved_fraction=round(saved_fraction, 3),
        )
    compile_bound = 2 * len(pre_buckets) + 1
    if ab["on"]["compilations"] > compile_bound:
        fail(  # the armed RecompileGuard should have raised first
            "prefix-cache engine exceeded the compile budget",
            compilations=ab["on"]["compilations"], bound=compile_bound,
        )

    # --- fleet serve-chaos + zero-downtime rollover (ISSUE 10 gates) ------
    fleet_metrics = _measure_serve_fleet(
        model, variables, prompts, n_requests=n_requests, max_new=max_new,
        slots=slots,
    )

    # --- paged KV + multi-tenant adapter gates (ISSUE 11) -----------------
    paged_metrics: dict = {}
    adapter_metrics: dict = {}
    if os.environ.get("BENCH_SERVE_PAGED", "1").strip().lower() not in (
            "0", "false", "no"):
        paged_metrics = _measure_serve_paged(
            model, variables, prompts, max_new=max_new,
        )
        adapter_metrics = _measure_serve_adapters(cfg, variables, max_new=max_new)

    # --- cross-process transport A/B + 64-concurrency gate (ISSUE 12) ----
    transport_metrics: dict = {}
    if os.environ.get("BENCH_SERVE_TRANSPORT", "1").strip().lower() not in (
            "0", "false", "no"):
        transport_metrics = _measure_serve_transport(max_new=max_new)

    return {
        "metric": f"serve_tokens_per_sec[{preset},req{n_requests},"
                  f"new{max_new},slots{slots}]",
        "value": round(engine_tps, 1),
        "unit": "tokens/sec",
        "speedup_vs_sequential": round(speedup, 2),
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "p50_latency_s": round(pct(engine_done, 50), 4),
        "p95_latency_s": round(pct(engine_done, 95), 4),
        "sequential_p50_latency_s": round(pct(seq_done, 50), 4),
        "sequential_p95_latency_s": round(pct(seq_done, 95), 4),
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "slots": slots,
        "compilations": engine.compilations,
        "recompile_budget": engine.guard.budget,
        # the timed windows above ran to completion, so an armed guard saw
        # ZERO device<->host syncs in the decode hot path (it aborts on one)
        "transfer_guard_armed": transfer_guard_armed,
        "transfer_guard_trips": (
            engine._transfer_guard.trips
            if engine._transfer_guard is not None else 0
        ),
        "mixed_prefix_on_tokens_per_sec": round(mixed_on_tps, 1),
        "prefix_ab": {
            "ttft_speedup": round(ttft_speedup, 2),
            "prefill_tokens_saved_fraction": round(saved_fraction, 3),
            "prefix_len": prefix_len,
            "cache_mb": cache_mb,
            **{f"{leg}_{k}": v for leg in ("off", "on")
               for k, v in ab[leg].items()},
        },
        "fleet": fleet_metrics,
        "paged": paged_metrics,
        "adapters": adapter_metrics,
        "transport": transport_metrics,
        "device_kind": jax.devices()[0].device_kind,
    }


def _measure_serve_transport(*, max_new) -> dict:
    """The ISSUE 12 cross-process gates, run inside ``BENCH_MODE=serve``:

    1. **scaling A/B**: the same 64+-concurrent mixed-length workload runs
       on four fleets — in-process 1 and N replicas, process-mode 1 and N
       workers.  On a multi-core host, N process workers must reach >= 1.5x
       the single worker's throughput AND beat the in-process N-replica
       ratio (in-process replicas share one JAX runtime, so their "scaling"
       is contention — measuring that baseline is part of the gate);
    2. **the deferred 64+-concurrent mixed-length latency gate** (ISSUE 10
       deferred it until replicas stopped sharing cores): every accepted
       request completes exactly once, and p95 completion latency on the
       N-worker process fleet stays within the fair-share queueing bound
       ``(conc / (workers * slots) + 2) x solo-request latency``.

    Every leg uses the deterministic ``tiny_test`` payload so in-process
    and worker processes decode identical weights.  Gates are enforced only
    on hosts with >= 2 cores per worker (BENCH notes in ROADMAP.md: this
    box is 2-CPU — numbers are recorded, the scaling assertion needs real
    cores); ``BENCH_SERVE_TRANSPORT=0`` skips the whole leg.
    """
    import asyncio
    import tempfile
    from pathlib import Path

    import numpy as np

    from finetune_controller_tpu.serve.engine import EngineConfig, GenRequest
    from finetune_controller_tpu.serve.fleet import ReplicaFleet
    from finetune_controller_tpu.serve.router import ReplicaRouter
    from finetune_controller_tpu.transport.builders import tiny_test
    from finetune_controller_tpu.transport.process import ProcessTransport

    conc = max(64, int(os.environ.get("BENCH_SERVE_CONC", "64")))
    workers = max(2, int(os.environ.get("BENCH_SERVE_TRANSPORT_WORKERS", "2")))
    slots = int(os.environ.get("BENCH_SERVE_TRANSPORT_SLOTS", "4"))
    new_tokens = min(max_new, 16)  # bounds the 4-leg wall clock
    ecfg = EngineConfig(slots=slots, prompt_buckets=(16, 32),
                        max_new_tokens=new_tokens + 8)
    model, variables = tiny_test()
    rng = np.random.default_rng(7)
    prompts = [
        [int(t) for t in rng.integers(1, model.cfg.vocab_size - 1, size=int(n))]
        for n in rng.integers(4, 30, size=conc)
    ]

    def reqs(tag, subset=None):
        chosen = prompts if subset is None else prompts[:subset]
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p,
                       max_new_tokens=new_tokens)
            for i, p in enumerate(chosen)
        ]

    def pct(xs, p):
        return float(np.percentile(np.asarray(xs), p))

    async def leg(mode: str, replicas: int, root) -> dict:
        if mode == "process":
            transport = ProcessTransport(
                job_id="bench-transport", root=Path(root),
                payload={"builder": "tiny_test", "kwargs": {}},
                spawn_timeout_s=600.0,
            )
            fleet = ReplicaFleet("bench-transport", None, None, ecfg,
                                 replicas=replicas, transport=transport)
        else:
            fleet = ReplicaFleet("bench-transport", model, variables, ecfg,
                                 replicas=replicas)
        t_spawn = time.perf_counter()
        await fleet.start()
        spawn_s = time.perf_counter() - t_spawn
        router = ReplicaRouter(fleet, default_timeout_s=600,
                               failover_retries=2)
        # engines warm-start at spawn; this wave warms the routing/RPC path
        await asyncio.gather(*(
            router.submit(r) for r in reqs("w", subset=replicas * slots)
        ))
        t1 = time.perf_counter()
        await router.submit(GenRequest(
            request_id="solo", tokens=prompts[0], max_new_tokens=new_tokens,
        ))
        solo_s = time.perf_counter() - t1
        lat: list[float] = []

        outputs: dict[str, list[int]] = {}

        async def one(r):
            t2 = time.perf_counter()
            res = await router.submit(r)
            lat.append(time.perf_counter() - t2)
            outputs[res.request_id] = [int(t) for t in res.generated]
            return len(res.generated)

        t0 = time.perf_counter()
        tokens = sum(await asyncio.gather(*(one(r) for r in reqs("m"))))
        window = time.perf_counter() - t0
        stats = fleet.stats()
        await fleet.close()
        completed_wave = len(lat)
        if completed_wave != conc:
            fail("transport leg lost requests", mode=mode,
                 replicas=replicas, completed=completed_wave, expected=conc)
        return {
            "tokens_per_sec": round(tokens / window, 1),
            "window_s": round(window, 3),
            "spawn_s": round(spawn_s, 2),
            "solo_latency_s": round(solo_s, 4),
            "p50_latency_s": round(pct(lat, 50), 4),
            "p95_latency_s": round(pct(lat, 95), 4),
            "transport": stats["transport"],
            "worker_pids": stats.get("worker_pids", []),
            "_outputs": outputs,
        }

    async def chaos_leg(root, baseline: dict[str, list[int]]) -> dict:
        """The serve-chaos satellite in PROCESS mode: the same
        ``FTC_FAULT_SERVE_*`` env, forwarded into the worker spawn, makes
        the victim REALLY SIGKILL itself mid-decode — exactly-once and
        bit-identity are then proven against genuine process death."""
        from finetune_controller_tpu.resilience.faults import ServeFault
        from finetune_controller_tpu.resilience.policy import RetryPolicy

        once = Path(root) / "fault-spent"
        transport = ProcessTransport(
            job_id="bench-transport-chaos", root=Path(root),
            payload={"builder": "tiny_test", "kwargs": {}},
            spawn_timeout_s=600.0,
            extra_env=ServeFault(
                replica_id="r0", at_step=2, mode="kill",
                once_file=str(once),
            ).to_env(),
        )
        fleet = ReplicaFleet(
            "bench-transport-chaos", None, None, ecfg, replicas=workers,
            transport=transport,
            restart_policy=RetryPolicy(max_attempts=3, base_delay_s=0.1,
                                       max_delay_s=0.3, seed=0),
        )
        await fleet.start()
        router = ReplicaRouter(fleet, default_timeout_s=600,
                               failover_retries=2)

        async def health_loop():
            while True:
                await fleet.health_tick()
                await asyncio.sleep(0.1)

        hl = asyncio.ensure_future(health_loop())
        try:
            results = await asyncio.gather(
                *(router.submit(r) for r in reqs("m", subset=16))
            )
            seen: dict[str, list[int]] = {}
            for r in results:
                if r.request_id in seen:
                    fail("process serve-chaos: request completed twice",
                         request_id=r.request_id)
                seen[r.request_id] = [int(t) for t in r.generated]
            if len(seen) != 16:
                fail("process serve-chaos: accepted requests were lost",
                     completed=len(seen))
            if not once.exists():
                fail("process serve-chaos: the forwarded SIGKILL fault "
                     "never fired")
            for rid, toks in seen.items():
                if toks != baseline.get(rid):
                    fail("process serve-chaos: output diverged from the "
                         "unkilled run", request_id=rid)
            stats = fleet.stats()
        finally:
            hl.cancel()
            await fleet.close()
        return {
            "real_sigkill": True,
            "exactly_once": True,
            "bit_identical_to_unkilled": True,
            "failovers": router.failovers_total,
            "replica_restarts": stats["replica_restarts_total"],
        }

    async def all_legs() -> dict:
        with tempfile.TemporaryDirectory(prefix="ftc-bench-transport-") as td:
            out = {
                "inproc_1r": await leg("inproc", 1, None),
                "inproc_multi": await leg("inproc", workers, None),
                "process_1w": await leg("process", 1, Path(td) / "w1"),
                "process_multi": await leg("process", workers, Path(td) / "wN"),
            }
            out["serve_chaos_process"] = await chaos_leg(
                Path(td) / "chaos", out["inproc_1r"]["_outputs"],
            )
            return out

    legs = asyncio.run(all_legs())
    chaos_process = legs.pop("serve_chaos_process")
    for doc in legs.values():
        doc.pop("_outputs", None)
    proc_ratio = (legs["process_multi"]["tokens_per_sec"]
                  / max(1e-9, legs["process_1w"]["tokens_per_sec"]))
    inproc_ratio = (legs["inproc_multi"]["tokens_per_sec"]
                    / max(1e-9, legs["inproc_1r"]["tokens_per_sec"]))
    # fair-share queueing bound for the latency gate: conc requests over
    # workers*slots lanes, two requests' slack for admission jitter
    waves = conc / (workers * slots) + 2
    latency_bound = waves * max(1e-3, legs["process_multi"]["solo_latency_s"])
    gates_enforced = (os.cpu_count() or 1) >= 2 * workers
    if gates_enforced:
        if proc_ratio < 1.5:
            fail("process-mode workers did not scale >= 1.5x",
                 process_ratio=round(proc_ratio, 2), workers=workers)
        if proc_ratio <= inproc_ratio:
            fail("process-mode scaling did not beat the in-process "
                 "contention baseline",
                 process_ratio=round(proc_ratio, 2),
                 inproc_ratio=round(inproc_ratio, 2))
        if legs["process_multi"]["p95_latency_s"] > latency_bound:
            fail("64-concurrent mixed-length p95 exceeded the fair-share "
                 "bound on process workers",
                 p95_s=legs["process_multi"]["p95_latency_s"],
                 bound_s=round(latency_bound, 3))
    return {
        "concurrency": conc,
        "workers": workers,
        "slots_per_replica": slots,
        "new_tokens": new_tokens,
        "process_scaling_x": round(proc_ratio, 2),
        "inproc_scaling_x": round(inproc_ratio, 2),
        "latency_gate_bound_s": round(latency_bound, 3),
        "gates_enforced": gates_enforced,
        "cpu_count": os.cpu_count(),
        "serve_chaos_process": chaos_process,
        "legs": legs,
    }


def _measure_serve_paged(model, variables, prompts, *, max_new) -> dict:
    """The ISSUE 11 paged-KV gates, run inside ``BENCH_MODE=serve``:

    1. **lanes-per-byte**: at a FIXED KV byte budget (the pool holds exactly
       the pages a ``slots_u``-lane unpaged cache would), the paged engine
       must run >= 2x ``slots_u`` concurrent mixed-length lanes — the
       capacity argument for paging: short requests stop paying full-length
       reservations;
    2. **throughput parity**: at EQUAL concurrency the paged engine's mixed
       workload must hold >= 0.9x the unpaged tokens/s (interleaved
       best-of-4 windows — the gather indirection must stay in the noise),
       with bit-identical greedy outputs.

    The parity RATIO is timing on a shared box, so it follows the ISSUE 12
    convention: enforced only with >= 2 cores per timed leg (4 cores — the
    two engines contend for the same runtime threads), recorded always
    (``gates_enforced`` in the metrics).  Bit-identity and the compile
    budget are load-independent and enforced everywhere.
    """
    import numpy as np

    from finetune_controller_tpu.serve.engine import (
        BatchEngine,
        EngineConfig,
        GenRequest,
    )

    page_tokens = int(os.environ.get("BENCH_SERVE_PAGE_TOKENS", "16"))
    buckets = (32, 128)
    slots_u = 4

    # --- gate 1: >= 2x concurrent lanes at a fixed byte budget ------------
    cfg_u = EngineConfig(slots=slots_u, prompt_buckets=buckets,
                         max_new_tokens=max_new + 8)
    pages_per_lane = -(-cfg_u.cache_len // page_tokens)
    budget_pages = slots_u * pages_per_lane   # == the unpaged cache's bytes
    cfg_p = EngineConfig(
        slots=4 * slots_u, prompt_buckets=buckets, max_new_tokens=max_new + 8,
        page_tokens=page_tokens, pool_pages=budget_pages + 1,
    )
    eng = BatchEngine(model, variables, cfg_p)
    rng = np.random.default_rng(7)
    short_prompts = [
        list(rng.integers(1, 200, size=int(n)))
        for n in rng.integers(4, 12, size=4 * slots_u)
    ]

    def short_reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p, max_new_tokens=8)
            for i, p in enumerate(short_prompts)
        ]

    eng.run(short_reqs("w"))  # warm: compiles land here
    pending = short_reqs("m")
    max_active = 0
    while pending or eng.active_requests:
        while pending and eng.free_slots and eng.can_admit(pending[0]):
            eng.admit(pending.pop(0))
        max_active = max(max_active, eng.active_requests)
        eng.step()
    if max_active < 2 * slots_u:
        fail(
            "paged engine below the 2x lanes-per-byte gate",
            max_concurrent_lanes=max_active, unpaged_lanes=slots_u,
            budget_pages=budget_pages, page_tokens=page_tokens,
        )

    # --- gate 2: >= 0.9x tokens/s at equal concurrency, bit-identical -----
    def mixed_reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p,
                       max_new_tokens=max_new)
            for i, p in enumerate(prompts)
        ]

    eng_u8 = BatchEngine(model, variables, EngineConfig(
        slots=8, prompt_buckets=buckets, max_new_tokens=max_new + 8))
    eng_p8 = BatchEngine(model, variables, EngineConfig(
        slots=8, prompt_buckets=buckets, max_new_tokens=max_new + 8,
        page_tokens=page_tokens))
    # interleave the legs (the obs-bench recipe): alternating short windows
    # cancel the box's slow drift, and best-of-N is robust because noise on
    # a shared CPU only ever makes a leg SLOWER, never faster
    tps_u = tps_p = 0.0
    out_u: dict = {}
    out_p: dict = {}
    for engine in (eng_u8, eng_p8):
        engine.run(mixed_reqs("w"))  # warm: compiles land outside timing
    for attempt in range(4):
        for which, engine in (("u", eng_u8), ("p", eng_p8)):
            t0 = time.perf_counter()
            out = engine.run(mixed_reqs(f"t{attempt}-"))
            window = time.perf_counter() - t0
            tps = sum(len(r.generated) for r in out.values()) / window
            if which == "u":
                tps_u, out_u = max(tps_u, tps), out
            else:
                tps_p, out_p = max(tps_p, tps), out
    for rid, r in out_u.items():
        if out_p[rid].generated != r.generated:
            fail("paged decode changed greedy output on the mixed workload",
                 request_id=rid)
    ratio = tps_p / tps_u
    gates_enforced = (os.cpu_count() or 1) >= 4
    if gates_enforced and ratio < 0.9:
        fail(
            "paged engine below the 0.9x throughput-parity gate",
            paged_tokens_per_sec=round(tps_p, 1),
            unpaged_tokens_per_sec=round(tps_u, 1),
            ratio=round(ratio, 3),
        )
    if eng_p8.compilations > eng_p8.guard.budget:
        fail(  # the armed RecompileGuard should have raised first
            "paged engine exceeded the compile budget",
            compilations=eng_p8.compilations, budget=eng_p8.guard.budget,
        )
    return {
        "page_tokens": page_tokens,
        "budget_pages": budget_pages,
        "max_concurrent_lanes_at_budget": max_active,
        "unpaged_lanes_at_budget": slots_u,
        "lanes_per_byte_gain": round(max_active / slots_u, 2),
        "paged_tokens_per_sec": round(tps_p, 1),
        "unpaged_tokens_per_sec": round(tps_u, 1),
        "throughput_ratio": round(ratio, 3),
        "gates_enforced": gates_enforced,
        "compilations": eng_p8.compilations,
        "recompile_budget": eng_p8.guard.budget,
        "tiering": _measure_serve_tiering(model, variables, max_new=max_new),
    }


def _measure_serve_tiering(model, variables, *, max_new) -> dict:
    """The ISSUE 16 host-KV-tier gates: tiering on vs off, everything else
    equal — same model, same prompts, same DEVICE page budget, same device
    prefix-cache budget.  The device prefix budget is set to HALF one
    entry's footprint, so the working set (3 shared prefixes) cannot live on
    the device at all: the off leg's cache refuses every insert and serves
    pure misses, the on leg births entries straight to host slots and pages
    them back in on touch.

    1. **capacity**: round 2 re-touches each of the 3 prefixes — the on leg
       must serve >= 2x the device-resident capacity (0 entries here, gate
       floor 2) as restore hits where the off leg records none.  Pure
       allocator arithmetic: enforced everywhere.
    2. **lanes**: a grouped wave (3 prefixes x 4 lanes) admitted until
       ``PoolExhausted`` at a pool sized to ~8 miss-lanes.  On-leg lanes
       share restored prefix pages (first lane of a group pays the full
       span, followers only the tail), off-leg lanes each reserve the full
       span, so admitted_on >= 1.5x admitted_off.  Allocator-deterministic:
       enforced everywhere.
    3. **throughput**: mixed touch rounds, interleaved best-of-4 — the on
       leg (restore + suffix prefill) must hold >= 0.8x the off leg's
       tokens/s.  Timing on a shared box: ISSUE 12 convention, enforced
       only with >= 4 cores (2 per timed leg), recorded always.

    Every request that runs in both legs must be bit-identical (demote /
    restore moves KV bytes, never changes them), and the decode windows run
    under the armed transfer guard — ``trips`` must stay 0 (tier d2h/h2d
    traffic lives in admission paths, never the decode dispatch).
    """
    import numpy as np

    from finetune_controller_tpu.serve.engine import (
        BatchEngine,
        EngineConfig,
        GenRequest,
    )
    from finetune_controller_tpu.serve.kv_pages import PoolExhausted

    page_tokens = int(os.environ.get("BENCH_SERVE_PAGE_TOKENS", "16"))
    buckets = (32, 128)
    prefix_len = max(buckets) - 1
    entry_pages = -(-max(buckets) // page_tokens)
    budget_pages = max(1, entry_pages // 2)  # device budget < one entry
    n_prefix, group = 3, 4

    probe = BatchEngine(model, variables, EngineConfig(
        slots=1, prompt_buckets=buckets, max_new_tokens=max_new + 8,
        page_tokens=page_tokens))
    page_bytes = probe._pool.page_bytes
    del probe

    rng = np.random.default_rng(16)
    prefixes = [list(rng.integers(1, 200, size=prefix_len))
                for _ in range(n_prefix)]

    def reqs(tag, tails, new_tokens):
        """One request per (prefix, tail): the shared 127-token prefix plus
        a distinct final token, so every prompt is a fresh cache KEY whose
        longest cached match is exactly the shared prefix."""
        return [
            GenRequest(request_id=f"{tag}-p{j}t{tl}",
                       tokens=prefixes[j] + [int(tl)],
                       max_new_tokens=new_tokens)
            for j in range(n_prefix) for tl in tails
        ]

    def make_engine(tiered: bool, slots: int, pool_pages: int):
        return BatchEngine(model, variables, EngineConfig(
            slots=slots, prompt_buckets=buckets,
            max_new_tokens=max_new + 8, page_tokens=page_tokens,
            pool_pages=pool_pages,
            prefix_cache_bytes=budget_pages * page_bytes,
            host_pool_bytes=(256 * page_bytes) if tiered else 0,
        ))

    # --- gates 1 + 3: capacity beyond the device budget, tok/s parity -----
    eng_on = make_engine(True, 4, 0)
    eng_off = make_engine(False, 4, 0)
    outs: dict[str, dict] = {"on": {}, "off": {}}
    hits_round2 = {}
    for which, eng in (("on", eng_on), ("off", eng_off)):
        outs[which].update(eng.run(reqs("r1", [210], max_new)))  # seed
        h0 = eng.prefix_hits_total
        outs[which].update(eng.run(reqs("r2", [211], max_new)))  # re-touch
        hits_round2[which] = eng.prefix_hits_total - h0
    if hits_round2["on"] < 2 * max(hits_round2["off"], 1):
        fail(
            "host tier below the 2x effective-prefix-capacity gate",
            round2_hits_tiered=hits_round2["on"],
            round2_hits_untiered=hits_round2["off"],
            working_set_entries=n_prefix,
            device_budget_pages=budget_pages, entry_pages=entry_pages,
        )

    tps_on = tps_off = 0.0
    for attempt in range(4):  # interleaved best-of-4, as in the paged gate
        for which, eng in (("on", eng_on), ("off", eng_off)):
            batch = reqs(f"t{attempt}", [220 + attempt, 230 + attempt],
                         max_new)
            t0 = time.perf_counter()
            out = eng.run(batch)
            window = time.perf_counter() - t0
            tps = sum(len(r.generated) for r in out.values()) / window
            if which == "on":
                tps_on = max(tps_on, tps)
            else:
                tps_off = max(tps_off, tps)
            outs[which].update(out)
    ratio = tps_on / tps_off
    gates_enforced = (os.cpu_count() or 1) >= 4
    if gates_enforced and ratio < 0.8:
        fail(
            "tiered decode below the 0.8x mixed tokens/s gate",
            tiered_tokens_per_sec=round(tps_on, 1),
            untiered_tokens_per_sec=round(tps_off, 1),
            ratio=round(ratio, 3),
        )

    # --- gate 2: >= 1.5x concurrent lanes at the same pool ----------------
    # pool sized to ~8 full-span miss lanes; the +8 span headroom keeps it
    # off lane-count boundaries for nearby page_tokens values
    span = max(buckets) + 8 - 1
    lane_pages = -(-span // page_tokens)
    lanes = {}
    wave_outs: dict[str, dict] = {}
    for which, tiered in (("on", True), ("off", False)):
        eng = make_engine(tiered, 2 * n_prefix * group, 8 * lane_pages)
        eng.run(reqs("seed", [240], 8))  # entries exist (host) / refused
        pending = reqs("wave", [250, 251, 252, 253], 8)
        admitted = []
        for req in pending:
            try:
                eng.admit(req)
            except PoolExhausted:
                break
            admitted.append(req.request_id)
        results: dict = {}
        while eng.active_requests:
            for r in eng.step():
                results[r.request_id] = r
        lanes[which] = len(admitted)
        wave_outs[which] = results
        if which == "on":
            tier_stats = eng.kv_page_stats()
            guard = eng._transfer_guard
    if lanes["on"] < 1.5 * lanes["off"]:
        fail(
            "host tier below the 1.5x concurrent-lanes gate",
            lanes_tiered=lanes["on"], lanes_untiered=lanes["off"],
            pool_pages=8 * lane_pages, lane_pages=lane_pages,
        )

    # --- bit-identity: every request served by BOTH legs must match -------
    for leg_on, leg_off, where in (
        (outs["on"], outs["off"], "mixed rounds"),
        (wave_outs["on"], wave_outs["off"], "lane wave"),
    ):
        for rid in set(leg_on) & set(leg_off):
            if leg_on[rid].generated != leg_off[rid].generated:
                fail("KV tiering changed greedy output "
                     f"({where})", request_id=rid)

    trips = guard.trips if guard is not None else None
    if trips:
        fail("transfer guard tripped inside the tiered decode window",
             trips=trips)
    return {
        "page_tokens": page_tokens,
        "device_prefix_budget_pages": budget_pages,
        "entry_pages": entry_pages,
        "working_set_entries": n_prefix,
        "round2_prefix_hits_tiered": hits_round2["on"],
        "round2_prefix_hits_untiered": hits_round2["off"],
        "lanes_admitted_tiered": lanes["on"],
        "lanes_admitted_untiered": lanes["off"],
        "lanes_gain": round(lanes["on"] / max(lanes["off"], 1), 2),
        "tiered_tokens_per_sec": round(tps_on, 1),
        "untiered_tokens_per_sec": round(tps_off, 1),
        "throughput_ratio": round(ratio, 3),
        "gates_enforced": gates_enforced,
        "demotions_total": tier_stats.get("demotions_total", 0),
        "restores_total": tier_stats.get("restores_total", 0),
        "host_pages_used": tier_stats.get("tier_host_pages_used", 0),
        "transfer_guard_trips": trips,
    }


def _measure_serve_adapters(cfg, variables, *, max_new) -> dict:
    """The ISSUE 11 multi-tenant gate: N adapters multiplexed UNMERGED on one
    engine produce outputs bit-identical to N dedicated single-tenant
    engines — the deployment alternative being displaced (one replica set
    per fine-tuned job).  Dedicated engines serve the same unmerged math: a
    merged-weights engine computes ``(W + sAB)x`` instead of
    ``Wx + s(xA)B``, which differs by floating-point reassociation (the
    logits agree to ~1e-6; argmax can flip on a tiny random-init model), so
    merged-vs-unmerged parity is pinned at the logits level in
    tests/test_serve_adapters.py rather than gated here."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaForCausalLM
    from finetune_controller_tpu.models.lora import LoRAConfig
    from finetune_controller_tpu.serve.engine import (
        BatchEngine,
        EngineConfig,
        GenRequest,
    )

    n_adapters = int(os.environ.get("BENCH_SERVE_ADAPTERS", "4"))
    page_tokens = int(os.environ.get("BENCH_SERVE_PAGE_TOKENS", "16"))
    base_cfg = cfg.replace(lora=LoRAConfig(rank=0))
    base_model = LlamaForCausalLM(base_cfg)
    base_vars = {"params": variables["params"]}

    # adapter stacks shaped by a rank-4 init; B nonzero so tenants diverge
    lora_shapes = jax.eval_shape(
        LlamaForCausalLM(cfg.replace(lora=LoRAConfig(rank=4))).init,
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4), jnp.int32),
    )["lora"]

    def make_adapter(seed):
        return jax.tree.map(
            lambda s: 0.05 * np.asarray(
                jax.random.normal(jax.random.PRNGKey(seed), s.shape),
                np.float32,
            ),
            lora_shapes,
        )

    adapters = {f"tenant-{i}": make_adapter(101 + i)
                for i in range(n_adapters)}
    rng = np.random.default_rng(11)
    prompts = {
        aid: list(rng.integers(1, 200, size=int(rng.integers(4, 20))))
        for aid in adapters
    }

    ecfg = EngineConfig(
        slots=max(4, n_adapters), prompt_buckets=(32, 128),
        max_new_tokens=max_new + 8, page_tokens=page_tokens,
        tenant_slots=n_adapters + 1, tenant_rank=8,
    )
    multi = BatchEngine(base_model, base_vars, ecfg)
    for aid, tree in adapters.items():
        multi.adapters.register(aid, tree, 16.0, 4)
        multi.install_adapter(aid)
    reqs = [
        GenRequest(request_id=f"m-{aid}", tokens=prompts[aid],
                   max_new_tokens=max_new, adapter_id=aid)
        for aid in adapters
    ]
    multi.run(reqs)  # warm
    t0 = time.perf_counter()
    res_multi = multi.run(reqs)
    multi_window = time.perf_counter() - t0

    dedicated = {}
    for aid, tree in adapters.items():
        eng = BatchEngine(base_model, base_vars, EngineConfig(
            slots=2, prompt_buckets=(32, 128), max_new_tokens=max_new + 8,
            page_tokens=page_tokens, tenant_slots=2, tenant_rank=8,
        ))
        eng.adapters.register(aid, tree, 16.0, 4)
        eng.install_adapter(aid)
        dedicated[aid] = eng.run([GenRequest(
            request_id="d", tokens=prompts[aid], max_new_tokens=max_new,
            adapter_id=aid,
        )])["d"].generated

    for aid in adapters:
        if res_multi[f"m-{aid}"].generated != dedicated[aid]:
            fail(
                "multiplexed output differs from the dedicated engine",
                adapter=aid,
            )
    distinct = len({tuple(r.generated) for r in res_multi.values()})
    if distinct < 2:
        fail(  # the per-lane gather must actually select different weights
            "multiplexed tenants produced identical outputs",
            distinct=distinct, adapters=n_adapters,
        )
    total_tokens = sum(len(r.generated) for r in res_multi.values())
    return {
        "adapters": n_adapters,
        "bit_identical_vs_dedicated": True,
        "distinct_outputs": distinct,
        "multiplexed_tokens_per_sec": round(total_tokens / multi_window, 1),
        "engines_displaced": n_adapters,  # one shared fleet instead of N
    }


def _measure_serve_fleet(model, variables, prompts, *, n_requests, max_new,
                         slots) -> dict:
    """The ISSUE 10 fleet gates, run inside ``BENCH_MODE=serve``:

    1. **serve-chaos**: a seeded replica kill mid-mixed-workload at 2+
       replicas — every accepted request must complete EXACTLY once with
       greedy outputs bit-identical to an unkilled fleet run (none lost,
       none duplicated);
    2. **zero-downtime rollover**: a checkpoint rollover under sustained
       load must complete with 0 failed requests and drain-window p99
       latency <= 2x steady state (plus a small absolute grace for CPU
       compile jitter on the tiny preset — new replicas pay their prefill
       compiles inside the window).

    Both legs share the seeded ``resilience/faults.py::ServeFault``
    injection path with the serve-chaos tests (``tests/test_serve_fleet.py``).
    """
    import asyncio

    import numpy as np

    from finetune_controller_tpu.resilience.faults import (
        ServeFault,
        ServeFaultInjector,
    )
    from finetune_controller_tpu.serve.engine import EngineConfig, GenRequest
    from finetune_controller_tpu.serve.fleet import ReplicaFleet
    from finetune_controller_tpu.serve.router import ReplicaRouter

    n_replicas = max(2, int(os.environ.get("BENCH_SERVE_REPLICAS", "2")))
    kill_step = int(os.environ.get("BENCH_SERVE_KILL_STEP",
                                   str(max(2, max_new // 2))))
    ecfg = EngineConfig(slots=slots, prompt_buckets=(32, 128),
                        max_new_tokens=max_new + 8)

    def reqs(tag, new_tokens=max_new):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p,
                       max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)
        ]

    def pct(xs, p):
        return float(np.percentile(np.asarray(xs), p))

    async def fleet_run(fault=None, tag="u"):
        fleet = ReplicaFleet("bench", model, variables, ecfg,
                             replicas=n_replicas, fault=fault)
        await fleet.start()
        router = ReplicaRouter(fleet, default_timeout_s=300,
                               failover_retries=2)
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *(router.submit(r) for r in reqs(tag))
        )
        window = time.perf_counter() - t0
        stats = fleet.stats()
        await fleet.close()
        return results, router, stats, window

    async def chaos_leg():
        baseline, _r, _s, _w = await fleet_run()
        base_tokens = {r.request_id[1:]: r.generated for r in baseline}
        fault = ServeFaultInjector(
            ServeFault(replica_id="r1", at_step=kill_step, mode="kill")
        )
        killed, router, stats, window = await fleet_run(fault=fault, tag="k")
        if not fault.fired:
            fail("serve-chaos kill never fired; raise the workload or "
                 "lower BENCH_SERVE_KILL_STEP", kill_step=kill_step)
        seen: dict[str, list[int]] = {}
        for r in killed:
            if r.request_id in seen:
                fail("serve-chaos: request completed twice",
                     request_id=r.request_id)
            seen[r.request_id] = r.generated
        if len(seen) != len(prompts):
            fail("serve-chaos: accepted requests were lost",
                 completed=len(seen), accepted=len(prompts))
        for rid, toks in seen.items():
            if toks != base_tokens[rid[1:]]:
                fail("serve-chaos: output diverged from the unkilled run",
                     request_id=rid)
        if stats["requests_completed_total"] != len(prompts):
            fail("serve-chaos: completion counter disagrees",
                 counted=stats["requests_completed_total"])
        return {
            "replicas": n_replicas,
            "kill_step": kill_step,
            "failovers": router.failovers_total,
            "step_errors": stats["step_errors_total"],
            "window_s": round(window, 3),
            "exactly_once": True,
            "bit_identical_to_unkilled": True,
        }

    async def rollover_leg():
        fleet = ReplicaFleet("bench-roll", model, variables, ecfg,
                             replicas=n_replicas)
        await fleet.start()
        router = ReplicaRouter(fleet, default_timeout_s=300,
                               failover_retries=2)
        failures: list[BaseException] = []

        async def wave(tag, lats):
            async def one(i, p):
                t1 = time.perf_counter()
                try:
                    await router.submit(GenRequest(
                        request_id=f"{tag}{i}", tokens=p, max_new_tokens=8,
                    ))
                    lats.append(time.perf_counter() - t1)
                except Exception as exc:
                    failures.append(exc)
            await asyncio.gather(
                *(one(i, p) for i, p in enumerate(prompts))
            )

        steady: list[float] = []
        for w in range(3):  # warm + steady-state sample
            await wave(f"s{w}-", steady if w else [])
        during: list[float] = []
        roll = asyncio.ensure_future(fleet.rollover(model, variables))
        w = 0
        while not roll.done():
            await wave(f"d{w}-", during)
            w += 1
        await roll
        # post-rollover sanity wave on the new generation
        await wave("post-", during)
        stats = fleet.stats()
        await fleet.close()
        if failures:
            fail("rollover dropped requests",
                 failed=len(failures), first=str(failures[0]))
        if stats["generation"] != 1 or stats["rollovers_total"] != 1:
            fail("rollover did not complete", **{
                k: stats[k] for k in ("generation", "rollovers_total")
            })
        p99_steady = pct(steady, 99)
        p99_during = pct(during, 99)
        # the 2x acceptance gate, with an absolute grace floor: on the tiny
        # CPU preset steady-state p99 is milliseconds, and the new
        # generation's prefill compiles land inside the drain window
        gate = max(2.0 * p99_steady, p99_steady + 0.75)
        if p99_during > gate:
            fail("rollover drain-window p99 exceeded 2x steady state",
                 p99_steady_s=round(p99_steady, 4),
                 p99_during_s=round(p99_during, 4))
        return {
            "failed_requests": 0,
            "p99_steady_s": round(p99_steady, 4),
            "p99_during_s": round(p99_during, 4),
            "p99_ratio": round(p99_during / max(p99_steady, 1e-9), 2),
            "drain_waves": w,
            "drains": stats["drains_total"],
        }

    async def both():
        return {
            "serve_chaos": await chaos_leg(),
            "rollover": await rollover_leg(),
        }

    return asyncio.run(both())


def main() -> None:
    if os.environ.get("BENCH_MODE", "").strip().lower() == "obs":
        # tracing-overhead gate: scale-free ratio on the tiny config, so it
        # runs on CPU by default like chaos/sched/dpo
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(_measure_obs()))
        return
    if os.environ.get("BENCH_MODE", "").strip().lower() == "chaos":
        # controller-plane bench: the parent process needs no accelerator —
        # the trainers run as subprocesses with their own JAX runtime
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(_measure_chaos_recovery()))
        return
    if os.environ.get("BENCH_MODE", "").strip().lower() == "sched":
        # scheduler-policy bench: pure simulator, no accelerator at all
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(_measure_sched()))
        return
    if os.environ.get("BENCH_MODE", "").strip().lower() == "dpo":
        # preference-optimization gates (docs/preference.md): the gates are
        # scale-free (margin trend + held-out accuracy on the tiny config),
        # so this runs on CPU by default like chaos/sched — pin
        # JAX_PLATFORMS=tpu explicitly to measure pair throughput on chips
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(_measure_dpo()))
        return
    import jax

    from finetune_controller_tpu.platform import enable_compile_cache, env_flag

    enable_compile_cache()

    if os.environ.get("BENCH_MODE", "").strip().lower() == "serve":
        result = _measure_serve()
        if jax.devices()[0].platform == "tpu":
            _session_log_append(result)
        print(json.dumps(result))
        return

    import numpy as np

    from finetune_controller_tpu.data.synthetic import synthetic_batches
    from finetune_controller_tpu.models.llama import PRESETS
    from finetune_controller_tpu.models.lora import LoRAConfig
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    devices = jax.devices()
    if devices[0].platform != "tpu":
        # tokens/sec/chip and MFU are device metrics: a run that finds no
        # chip fails — it never measures the CPU under their names
        fail("no TPU found: the training bench measures a chip",
             platform=devices[0].platform, device_kind=devices[0].device_kind)
    peak = _peak_tflops(devices[0].device_kind)
    tiny = env_flag("BENCH_TINY")

    n_chips = len(devices)
    # Default global batch must divide evenly over the fsdp=all-chips mesh,
    # so scale it with the chip count (a v5e-16 slice gets batch 16, not 8).
    default_batch = max(8, n_chips)
    # BENCH_MODE selects the BASELINE config family:
    #   lora (default) — config #1 (TinyLlama LoRA)
    #   qlora          — config #3 (int4 frozen base; a 7B fits one v5e chip)
    #   mm             — config #5 (LLaVA multimodal SFT; int4 text tower +
    #                    bf16 ViT — that combination fits one chip)
    #   moe            — config #4 proxy (Mixtral-architecture 8-expert top-2
    #                    at single-chip scale, bf16 frozen base; MFU uses
    #                    active_param_count so idle experts earn no credit)
    mode = os.environ.get("BENCH_MODE", "lora").strip().lower()
    qlora = mode == "qlora"
    mm = mode == "mm"
    moe = mode == "moe"
    if tiny:
        preset = os.environ.get(
            "BENCH_PRESET",
            "tiny-mm-test" if mm else ("tiny-moe-test" if moe else "tiny-test"),
        )
        batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))
        seq = int(os.environ.get("BENCH_SEQ", "128"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        lora = LoRAConfig(rank=8)
    elif mm:
        preset = os.environ.get("BENCH_PRESET", "llava-1.5-7b")
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        # seq = TEXT tokens; the decoder additionally attends the 576-patch
        # image prefix, which the FLOP accounting below includes
        seq = int(os.environ.get("BENCH_SEQ", "1472"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        lora = LoRAConfig(rank=16)
    elif moe:
        preset = os.environ.get("BENCH_PRESET", "mixtral-proxy")
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        lora = LoRAConfig(rank=16)
    else:
        preset = os.environ.get(
            "BENCH_PRESET", "mistral-7b" if qlora else "tinyllama-1.1b"
        )
        batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        steps = int(os.environ.get("BENCH_STEPS", "20"))
        lora = LoRAConfig(rank=16)

    if mm:
        from finetune_controller_tpu.models.multimodal import MM_PRESETS

        base_presets = MM_PRESETS
    else:
        base_presets = PRESETS
    model_cfg = base_presets[preset].replace(lora=lora, max_seq_len=max(seq, 128))
    if qlora or (mm and not tiny):
        # int4 base; the d_ff-wide "mlp" remat saves don't fit next to a 7B
        # model's activations on one chip — full recompute is the measured
        # config (override via BENCH_REMAT_POLICY to experiment). For mm the
        # quantization covers the frozen text tower (the ViT + projector are
        # plain flax Dense and ride the bf16 frozen cast instead).
        model_cfg = model_cfg.replace(quantize_base=True, remat_policy="full")
    if os.environ.get("BENCH_REMAT_POLICY"):
        model_cfg = model_cfg.replace(remat_policy=os.environ["BENCH_REMAT_POLICY"])
    if os.environ.get("BENCH_ATTN_IMPL"):
        model_cfg = model_cfg.replace(attention_impl=os.environ["BENCH_ATTN_IMPL"])
    if os.environ.get("BENCH_LOGITS_DTYPE"):
        import jax.numpy as _jnp

        model_cfg = model_cfg.replace(
            logits_dtype=_jnp.dtype(os.environ["BENCH_LOGITS_DTYPE"])
        )
    probe_steps = min(5, steps)  # individually-blocked spread probe
    mesh = MeshSpec(fsdp=-1).build(devices)
    # bf16 storage for the frozen base halves its HBM footprint (measured
    # ~1% step win on its own, and the headroom is what lets the "mlp" remat
    # policy fit); the tiny leg keeps f32 for checkpoint-test parity
    frozen_default = "bfloat16" if not tiny else ""
    train_cfg = TrainConfig(
        mode="lora", batch_size=batch, seq_len=seq,
        # 3 warmup + the individually-blocked probe window + the timed window
        # must all fit inside the LR schedule (steps past total_steps would
        # train at the clamped min-LR floor, not the declared regime)
        total_steps=steps + 3 + probe_steps,
        log_every=10**9, checkpoint_every=10**9,
        frozen_dtype=os.environ.get("BENCH_FROZEN_DTYPE", frozen_default) or None,
        # recompilation guard (analysis/recompile_guard.py): a step that
        # recompiles mid-window is a measurement bug (the timed window would
        # include XLA compiles), so the bench RAISES instead of printing a
        # slow number. Budget 0 disables; the default of 4 covers every batch
        # structure a bench run legitimately produces (text window, mm A/B
        # legs) while a per-step shape leak burns through it immediately.
        recompile_budget=int(os.environ.get("BENCH_RECOMPILE_BUDGET", "4")),
        recompile_action="raise",
        # transfer guard (analysis/transfer_guard.py): same contract for
        # device<->host syncs — a stray device_get / implicit np transfer
        # inside the timed step window ABORTS the bench instead of silently
        # serializing the dispatch pipeline. BENCH_TRANSFER_GUARD=0 disables.
        transfer_guard=(
            "raise" if env_flag("BENCH_TRANSFER_GUARD", default=True)
            else "off"
        ),
        # shard audit (analysis/shard_audit.py): state leaves that lose
        # their rule-table sharding pay a silent GSPMD reshard every step —
        # a slow number that is a BUG, not a result. Armed, a mis-sharded
        # run ABORTS. BENCH_SHARD_AUDIT=0 disables.
        shard_audit=(
            "raise" if env_flag("BENCH_SHARD_AUDIT", default=True)
            else "off"
        ),
    )
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state = trainer.init_state()
    image_size = model_cfg.image_size  # 0 on text-only configs
    batches = synthetic_batches(
        batch, seq, model_cfg.vocab_size, seed=0,
        task="brightness" if mm else "increment",
        image_size=image_size,
    )
    # background input prefetch (data/prefetch.py) — the trainer-path default;
    # BENCH_PREFETCH=0 measures the synchronous legacy pipeline
    from finetune_controller_tpu.data.prefetch import prefetch_batches

    prefetch_depth = int(os.environ.get("BENCH_PREFETCH", "2"))
    batches = prefetch_batches(
        batches, depth=prefetch_depth, transfer=trainer.shard_batch
    )

    # Warmup: first step compiles; two more reach dispatch steady-state.
    warmup_losses = []
    for _ in range(3):
        state, metrics = trainer.step(state, next(batches))
        state = jax.block_until_ready(state)
        warmup_losses.append(float(metrics["loss"]))

    # Spread probe: a few individually-blocked steps expose per-step jitter
    # (compile stragglers, host hiccups) that the overlapped window hides.
    probe_times: list[float] = []
    timed_losses: list[float] = []
    for _ in range(probe_steps):
        step_batch = next(batches)
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, step_batch)
        state = jax.block_until_ready(state)
        probe_times.append(time.perf_counter() - t0)
        timed_losses.append(float(metrics["loss"]))

    # Timed window: dispatch all steps, block once on the final state — the
    # throughput an uninstrumented training loop achieves, with every step's
    # device work still forced to complete inside the window.  The input wait
    # (time blocked on next(batches)) is accounted separately: its share of
    # the window is the input_fraction the JSON reports.
    t0 = time.perf_counter()
    window_metrics = []
    input_s = 0.0
    for _ in range(steps):
        t_in = time.perf_counter()
        step_batch = next(batches)
        input_s += time.perf_counter() - t_in
        state, metrics = trainer.step(state, step_batch)
        window_metrics.append(metrics)
    state = jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    timed_losses += [float(m["loss"]) for m in window_metrics]
    if hasattr(batches, "close"):
        batches.close()

    # shard audit over the FINAL live state (the checkpoint-boundary trap,
    # run explicitly here since the bench never checkpoints): every device
    # leaf must still carry its rule-table NamedSharding after the timed
    # window, or the measured number was taxed by silent resharding
    if trainer._shard_auditor is not None:
        trainer._audit_state_sharding(state, "bench-final-state")

    # --- sanity: the steps must have done real optimization work -----------
    if not all(np.isfinite(warmup_losses + timed_losses)):
        fail("non-finite loss", warmup_losses=warmup_losses, timed_losses=timed_losses)
    if float(np.mean(timed_losses)) > float(np.mean(warmup_losses)) + 0.5:
        fail(
            "timed-window loss regressed above warmup — step is not optimizing",
            warmup_losses=warmup_losses, timed_losses=timed_losses,
        )

    med = window_s / steps
    p10 = float(np.percentile(probe_times, 10))
    p90 = float(np.percentile(probe_times, 90))
    tokens_per_step = batch * seq
    tok_per_sec_chip = tokens_per_step / med / n_chips

    if mm:
        # tokens = TEXT tokens, but the step's FLOPs also cover the decoder
        # attending the image prefix and the ViT+projector encoding it —
        # fold that into flops_per_(text-)token so the MFU stays honest
        patches = model_cfg.vision.n_patches
        n_text = model_cfg.text.param_count()
        n_vision = model_cfg.param_count() - n_text
        flops_per_step = 6.0 * (
            n_text * batch * (seq + patches) + n_vision * batch * patches
        )
        flops_per_token = flops_per_step / tokens_per_step
    else:
        # active_param_count == param_count on dense configs; on MoE it
        # counts the router + top-k experts a token actually runs through.
        # NOTE: capacity-factor padding means the expert einsums execute over
        # e*capacity slots (≈ capacity_factor × the credited k·T rows), so
        # executed FLOPs exceed this figure by ~capacity_factor on the expert
        # share — MoE MFU here is a deliberate LOWER BOUND (useful-work MFU:
        # padding slots earn no credit). Keep that in mind when tuning
        # against these numbers.
        flops_per_token = 6.0 * model_cfg.active_param_count()
    achieved_flops = tok_per_sec_chip * flops_per_token
    target = TARGET_MFU * peak * 1e12 / flops_per_token
    mfu = achieved_flops / (peak * 1e12)
    # --- a >100% MFU figure is a measurement bug, not a result -------------
    # (e.g. an async runtime making steps look free)
    if mfu > 1.0:
        fail(
            "achieved MFU > 1.0 — physically impossible, measurement invalid",
            mfu=round(mfu, 3),
            tok_per_sec_chip=round(tok_per_sec_chip, 1),
            step_time_avg_s=med,
            probe_step_p10_s=p10,
            probe_step_p90_s=p90,
            device_kind=devices[0].device_kind,
            peak_tflops=peak,
        )

    kind = "qlora" if qlora else ("mm_lora" if mm else ("moe_lora" if moe else "lora"))
    result = {
        "metric": f"{kind}_sft_tokens_per_sec_per_chip"
                  f"[{preset},bs{batch},seq{seq}]",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tok_per_sec_chip / target, 3),
        "mfu": round(mfu, 4),
        "step_time_avg_s": round(med, 4),
        "probe_step_p10_s": round(p10, 4),
        "probe_step_p90_s": round(p90, 4),
        "prefetch_depth": prefetch_depth,
        "input_ms_avg": round(input_s / steps * 1000, 3),
        "input_fraction": round(input_s / window_s, 4),
        "n_chips": n_chips,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "warmup_loss_mean": round(float(np.mean(warmup_losses)), 4),
        "timed_loss_mean": round(float(np.mean(timed_losses)), 4),
        # the audit above ran to completion under action="raise", so an
        # armed run reaching this line proves zero violations
        "shard_audit_armed": trainer._shard_auditor is not None,
        "shard_audit_checks": (
            trainer._shard_auditor.checks
            if trainer._shard_auditor is not None else 0
        ),
        "shard_audit_violations": (
            trainer._shard_auditor.violations
            if trainer._shard_auditor is not None else 0
        ),
    }
    if mm and env_flag("BENCH_PREFETCH_AB", default=True):
        # prefetch off/on A/B over REAL decoded images (BASELINE #5's "mixed
        # host-image pipeline"): measured, not asserted — the JSON carries
        # both legs so a regression in the overlap is visible per round
        import tempfile

        with tempfile.TemporaryDirectory(prefix="ftc_mm_bench_") as d:
            ds = _write_mm_bench_dataset(
                d, n_rows=max(3 * batch, 24),
                src_px=max(512, 2 * image_size),
            )
            state, result["prefetch_ab"] = measure_mm_prefetch_ab(
                trainer, state, ds, image_size=image_size,
                batch=batch, seq=seq,
                steps=min(8, steps), depth=max(prefetch_depth, 1),
            )

    _session_log_append(result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
