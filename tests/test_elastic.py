"""Elastic / fault-tolerant training tests (SURVEY.md §5.3-§5.4 — the gap the
TPU build must close; the reference delegates recovery entirely to K8s
restartPolicy and has no resume).

Two layers:

* e2e: a running local-backend job is killed mid-run (``inject_fault``, the
  spot-preemption stand-in); asserted path is RESTARTING → resume from
  checkpoint → SUCCEEDED with step-continuous metrics.
* multi-process: a real 2-process ``jax.distributed`` CPU run exercising the
  collective code paths that otherwise only run in their degenerate
  single-process form — ``state_to_host`` allgather, rank-0-authoritative
  broadcast-resume, and ``_sync_preemption``.
"""

import asyncio
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from conftest import run_async as run
from finetune_controller_tpu.controller.backends.local import LocalProcessBackend
from finetune_controller_tpu.controller.examples import LoRASFTArguments, TinyTestLoRA
from finetune_controller_tpu.controller.monitor import JobMonitor
from finetune_controller_tpu.controller.objectstore import LocalObjectStore
from finetune_controller_tpu.controller.schemas import (
    BackendJobState,
    DatabaseStatus,
    JobInput,
)
from finetune_controller_tpu.controller.statestore import StateStore
from finetune_controller_tpu.controller.task_builder import DatasetInput, task_builder

from conftest import one_chip_catalog


def test_fault_injection_restart_resume_e2e(tmp_path):
    """Kill the training process mid-run; the job must restart, resume from
    the checkpoint (not step 0), and finish SUCCEEDED with continuous
    metrics."""

    async def main():
        state = StateStore(tmp_path / "state")
        store = LocalObjectStore(tmp_path / "objects")
        catalog = one_chip_catalog()
        backend = LocalProcessBackend(
            tmp_path / "sandboxes", store, catalog, sync_interval_s=0.2
        )
        monitor = JobMonitor(state, store, backend, interval_s=0.1)
        await state.connect()

        total_steps = 2000
        ckpt_every = 100
        spec = TinyTestLoRA(
            training_arguments=LoRASFTArguments(
                total_steps=total_steps, warmup_steps=1, batch_size=2,
                seq_len=16, lora_rank=2,
            )
        )
        # log/checkpoint cadence rides through build_trainer_spec overrides
        job = JobInput(
            job_id="elastic-1", user_id="u", model_name="tiny-test-lora",
            device="chip-1",
            arguments={"total_steps": total_steps},
        )
        trainer_overrides = {"log_every": ckpt_every, "checkpoint_every": ckpt_every}

        await task_builder(
            job, spec, DatasetInput(),
            state=state, store=store, backend=backend, catalog=catalog,
            datasets_bucket="datasets", artifacts_bucket="artifacts",
        )
        # patch cadence into the rendered spec (the submit path fixes
        # log_every via spec args; edit the sandbox spec directly for the test)
        handle = backend._handles["elastic-1"]
        rendered = json.loads(handle.spec_path.read_text())
        rendered["training"].update(trainer_overrides)
        handle.spec_path.write_text(json.dumps(rendered))

        # wait for the first checkpoint, then preempt (SIGTERM, what a TPU
        # spot reclaim sends)
        ckpt_dir = handle.artifacts_dir / "checkpoints"
        deadline = time.monotonic() + 150
        while not any(ckpt_dir.glob("step_*")):
            assert time.monotonic() < deadline, "no checkpoint appeared"
            await asyncio.sleep(0.3)
        assert await backend.inject_fault("elastic-1", signum=15)

        # the backend must pass through RESTARTING on its way back up
        saw_restarting = False
        report = None
        deadline = time.monotonic() + 240
        while True:
            report = await backend.get_job("elastic-1")
            assert report is not None
            if report.state is BackendJobState.RESTARTING:
                saw_restarting = True
            if report.state in (BackendJobState.SUCCEEDED, BackendJobState.FAILED):
                break
            assert time.monotonic() < deadline, report
            await asyncio.sleep(0.1)
        assert report.state is BackendJobState.SUCCEEDED, report
        assert report.metadata["restarts"] == 1
        assert saw_restarting or report.metadata["restarts"] == 1

        # resume proof: training log shows the second attempt resuming from a
        # checkpoint step, not starting at 0
        log_text = (handle.sandbox / "logs.txt").read_text()
        assert "resumed from checkpoint step" in log_text

        # metrics are step-continuous across the restart: every cadence row
        # present once, up to total_steps
        metrics_csv = (handle.artifacts_dir / "metrics.csv").read_text().splitlines()
        steps = [int(row.split(",")[1]) for row in metrics_csv[1:]]
        # column order: timestamp,step,... — find the step column robustly
        header = metrics_csv[0].split(",")
        si = header.index("step")
        steps = [int(float(row.split(",")[si])) for row in metrics_csv[1:]]
        assert steps[-1] == total_steps
        assert steps == sorted(set(steps)), "duplicate or out-of-order metric rows"
        expected = list(range(ckpt_every, total_steps + 1, ckpt_every))
        assert [s for s in steps if s % ckpt_every == 0] == expected

        # monitor reconciles the DB to SUCCEEDED
        await monitor.tick()
        rec = await state.get_job("elastic-1")
        assert rec.status is DatabaseStatus.SUCCEEDED
        await backend.close()
        await state.close()

    run(main())


_DIST_DRIVER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")

rank = int(sys.argv[1])
port = sys.argv[2]
art_root = sys.argv[3]
jax.distributed.initialize(f"localhost:{port}", num_processes=2, process_id=rank)
assert jax.process_count() == 2, jax.process_count()

import numpy as np
from finetune_controller_tpu.models.llama import PRESETS
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.parallel.mesh import MeshSpec
from finetune_controller_tpu.train.trainer import TrainConfig, Trainer
from finetune_controller_tpu.data.synthetic import synthetic_batches

cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=2))
tc = TrainConfig(mode="lora", learning_rate=0.01, total_steps=6, batch_size=4,
                 seq_len=16, log_every=3, checkpoint_every=3)
trainer = Trainer(cfg, tc, mesh=MeshSpec(fsdp=2).build())

# rank-0-authoritative artifacts: only rank 0's dir receives checkpoints,
# rank 1 must learn the resume step via the broadcast
art = os.path.join(art_root, f"rank{rank}")
os.makedirs(art, exist_ok=True)

batches = synthetic_batches(trainer.local_batch_size, 16, cfg.vocab_size,
                            seed=rank)
state = trainer.fit(batches, art, resume=False)

# --- state_to_host: collective allgather must agree across ranks ----------
host = trainer.state_to_host(state)
step_val = int(host["step"])
l2 = float(np.sqrt(sum(float((x.astype(np.float64) ** 2).sum())
                       for x in jax.tree.leaves(host["trainable"]))))
print(f"RANK{rank} STEP {step_val} L2 {l2:.6f}", flush=True)

# --- _sync_preemption: any-rank flag ORs to all ranks ---------------------
got = trainer._sync_preemption(rank == 1)
assert got is True, f"rank {rank}: preemption OR failed"
got0 = trainer._sync_preemption(False)
assert got0 is False
print(f"RANK{rank} PREEMPT_OK", flush=True)

# --- broadcast-resume: rank 0 has the checkpoint, rank 1 does not ---------
tc2 = TrainConfig(mode="lora", learning_rate=0.01, total_steps=9, batch_size=4,
                  seq_len=16, log_every=3, checkpoint_every=3)
trainer2 = Trainer(cfg, tc2, mesh=MeshSpec(fsdp=2).build())
batches2 = synthetic_batches(trainer2.local_batch_size, 16, cfg.vocab_size,
                             seed=rank)
state2 = trainer2.fit(batches2, art, resume=True)
host2 = trainer2.state_to_host(state2)
print(f"RANK{rank} RESUMED_TO {int(host2['step'])}", flush=True)
"""


def test_two_process_distributed_cpu(tmp_path):
    """Real 2-process jax.distributed run: allgather state_to_host, preemption
    OR-sync, and rank-0-authoritative broadcast resume."""
    driver = tmp_path / "driver.py"
    driver.write_text(_DIST_DRIVER)
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(driver), str(r), str(port), str(tmp_path / "art")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=420)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    # both ranks agree on the gathered state (same step, same L2 norm)
    lines = {r: dict() for r in range(2)}
    for r, out in enumerate(outs):
        for tok in out.splitlines():
            if tok.startswith(f"RANK{r} STEP"):
                parts = tok.split()
                lines[r]["step"], lines[r]["l2"] = int(parts[2]), float(parts[4])
            if tok.startswith(f"RANK{r} RESUMED_TO"):
                lines[r]["resumed"] = int(tok.split()[2])
        assert f"RANK{r} PREEMPT_OK" in out, out[-2000:]
    assert lines[0]["step"] == lines[1]["step"] == 6
    assert abs(lines[0]["l2"] - lines[1]["l2"]) < 1e-6
    # rank 1 had no checkpoint files locally, yet resumed to the final step
    # because rank 0's view was broadcast
    assert lines[0]["resumed"] == lines[1]["resumed"] == 9
    rank1_ckpts = Path(tmp_path / "art" / "rank1" / "checkpoints")
    rank0_ckpts = Path(tmp_path / "art" / "rank0" / "checkpoints")
    assert any(rank0_ckpts.glob("step_*"))
