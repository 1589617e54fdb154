"""The checkpoint library stays out of start-up.

``train/checkpoint.py`` imports ``orbax.checkpoint`` where a checkpoint is
first written or read, on the thread that does it: the library pulls in
``google.cloud.logging``, whose packages scan every installed distribution
twice as they are imported (~11 s on a chip's host, PERF.md §6), and a job's
start must not pay that.  The sys.modules cases run in a child process: this
one may have the library loaded by an earlier test.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from finetune_controller_tpu.train.checkpoint import CheckpointManager

#: the library and what it drags in.  The bare ``google`` and ``google.cloud``
#: namespaces are made by a ``.pth`` file as the interpreter starts, in every
#: process, so it is their CHILDREN that say an import happened.
HEAVY = ("orbax", "google.cloud.", "google.api_core")

_PRELUDE = f"""
import json, sys
HEAVY = {HEAVY!r}
def heavy():
    return sorted(m for m in sys.modules if m.startswith(HEAVY))
def tiny_trainer(**cfg):
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.train import Trainer, TrainConfig
    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=2))
    return Trainer(model_cfg, TrainConfig(
        mode="lora", learning_rate=1e-3, warmup_steps=1, batch_size=2,
        seq_len=16, log_every=100, prefetch=0, heartbeat_interval_s=0,
        **cfg)), model_cfg
"""


def _child(body: str, *argv: str, env: dict | None = None) -> dict:
    """Run ``body`` after the prelude in a fresh interpreter; its last line
    of output is one JSON object."""
    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE + body, *argv],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_CASES = {
    "import_trainer": """
import finetune_controller_tpu.train.trainer
print(json.dumps({"heavy": heavy()}))
""",
    "latest_step_of_an_empty_directory": """
from finetune_controller_tpu.train.checkpoint import CheckpointManager
mgr = CheckpointManager(sys.argv[1])
assert mgr.latest_step() is None
print(json.dumps({"heavy": heavy()}))
""",
    "trainer_built": """
tiny_trainer(total_steps=2)
print(json.dumps({"heavy": heavy()}))
""",
    "one_blocking_save": """
import numpy as np
from finetune_controller_tpu.train.checkpoint import CheckpointManager
mgr = CheckpointManager(sys.argv[1])
tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4), "step": np.int32(7)}
before = heavy()
mgr.save(7, tree, blocking=True)
back = CheckpointManager(sys.argv[1]).restore(7)
print(json.dumps({
    "before": before, "heavy": heavy(),
    "thread": mgr.backend_import_thread, "import_s": mgr.backend_import_s,
    "equal": bool(np.array_equal(back["w"], tree["w"]))
             and int(back["step"]) == 7,
}))
""",
}


@pytest.mark.parametrize("case", list(_CASES))
def test_the_checkpoint_library_is_imported_by_the_first_save_only(
        case, tmp_path):
    out = _child(_CASES[case], str(tmp_path / "ckpt"))
    if case != "one_blocking_save":
        assert out["heavy"] == []
        return
    assert out["before"] == []
    assert "orbax.checkpoint" in out["heavy"]
    assert out["equal"]
    # even a blocking save's import is its writer's, not the caller's
    assert out["thread"] == "checkpoint-writer"
    assert out["import_s"] > 0


def test_first_saves_import_runs_on_the_writer_after_save_returned(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    # a wait() with nothing in flight touches no library
    mgr.wait()
    assert mgr._ckptr_obj is None and mgr.backend_import_thread is None

    gate = threading.Event()
    real = mgr._save_sync

    def gated(*args):
        assert gate.wait(timeout=60)
        real(*args)

    mgr._save_sync = gated
    committed = []
    tree = {"w": np.ones((2, 3), np.float32)}
    mgr.save(1, tree, on_commit=lambda: committed.append(
        threading.current_thread().name))
    # save() is back, and nobody has asked for the library yet
    assert mgr._ckptr_obj is None and mgr.backend_import_thread is None
    assert mgr.take_backend_import() == {
        "backend_import_s": 0.0, "backend_import_thread": None}
    gate.set()
    mgr.wait()
    assert mgr.backend_import_thread == "checkpoint-writer"
    assert threading.current_thread().name != "checkpoint-writer"
    assert committed == ["checkpoint-writer"]
    first = mgr.take_backend_import()
    assert first["backend_import_thread"] == "checkpoint-writer"
    assert first["backend_import_s"] >= 0
    # reported once
    assert mgr.take_backend_import() == {
        "backend_import_s": 0.0, "backend_import_thread": None}
    np.testing.assert_array_equal(mgr.restore(1)["w"], tree["w"])


def test_a_failed_save_reports_no_commit(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"))

    def boom(*args, **kwargs):
        raise OSError("disk full")

    mgr._write_manifest = boom
    committed = []
    mgr.save(1, {"w": np.ones(2, np.float32)}, manifest={"k": 1},
             on_commit=lambda: committed.append(1))
    with pytest.raises(RuntimeError, match="disk full"):
        mgr.wait()
    assert committed == []


_FIT = """
import threading
from finetune_controller_tpu.data import synthetic_batches
from finetune_controller_tpu.obs.events import parse_event_lines
from finetune_controller_tpu.obs.trace import parse_span_lines
art = sys.argv[1]
trainer, model_cfg = tiny_trainer(total_steps=4, checkpoint_every=2)
before = heavy()
batches = synthetic_batches(2, 16, model_cfg.vocab_size, task="increment")
trainer.fit(batches, art, resume=False)
after = heavy()
# a second job on the same artifacts resumes: its restore needs the library
trainer, _ = tiny_trainer(total_steps=6, checkpoint_every=2)
trainer.fit(batches, art, resume=True)
events = parse_event_lines(open(art + "/events.jsonl").read())
spans = parse_span_lines(open(art + "/trace/trainer.jsonl").read())
print(json.dumps({
    "before": before, "loaded": "orbax.checkpoint" in after,
    "main": threading.current_thread().name,
    "committed": [e["attrs"] for e in events
                  if e["event"] == "checkpoint-committed"],
    "restore": [s["attributes"] for s in spans if s["name"] == "restore"],
}))
"""


def test_first_checkpoint_committed_event_carries_the_import(tmp_path):
    # spans are written only under a trace identity
    out = _child(_FIT, str(tmp_path),
                 env={"FTC_TRACE": "1", "FTC_TRACE_ID": "c" * 32})
    assert out["before"] == [] and out["loaded"]
    first, second, third = out["committed"]
    # the first save of the job: async, its writer paid the import
    assert first["step"] == 2 and "blocking" not in first
    assert first["backend_import_s"] > 0
    assert first["backend_import_thread"] == "checkpoint-writer"
    # the last save of the job: nothing left to pay
    assert second["step"] == 4 and second["blocking"] is True
    assert second["backend_import_s"] == 0.0
    assert "backend_import_thread" not in second
    # the resumed job paid at its restore, on the caller's thread, and says
    # so on the span it already wrote; its own save has nothing left to pay
    (restore,) = out["restore"]
    assert restore["step"] == 4
    assert restore["backend_import_thread"] == out["main"]
    assert restore["backend_import_s"] >= 0
    assert third["step"] == 6 and third["backend_import_s"] == 0.0


_WARM = """
import io, os
from finetune_controller_tpu.train import warm_worker
seen = {}
class Stdin(io.StringIO):
    def readline(self):
        # the worker asks for its request right after it reports ready
        seen["ready"] = os.path.exists(os.environ["FTC_WARM_READY_FILE"])
        seen["loaded"] = "orbax.checkpoint" in sys.modules
        return ""  # the pool's shutdown signal
sys.stdin = Stdin()
rc = warm_worker.main()
print(json.dumps({"rc": rc, **seen}))
"""


def test_warm_worker_has_the_checkpoint_library_when_it_reports_ready(tmp_path):
    out = _child(_WARM, env={"FTC_WARM_READY_FILE": str(tmp_path / "ready")})
    assert out == {"rc": 0, "ready": True, "loaded": True}
