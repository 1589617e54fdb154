"""Pre-warmed trainer process: eat the JAX import + backend-init cost
*before* a job arrives.

``python -m finetune_controller_tpu.train.warm_worker`` imports JAX and
initialises the platform backend immediately, then blocks on stdin until the
local backend hands it one request line:

    {"spec": "/path/job.json", "log": "/path/logs.txt", "cwd": "/sandbox"}

It then redirects stdout/stderr to the job's log file (the same file a
cold-spawned trainer would write), chdirs into the sandbox, and runs the job
via ``train.cli``.  One request per process — the pool replaces used workers.

Why: the submit -> first-training-step span is dominated by interpreter +
JAX import and backend init (~8-25 s measured; `BASELINE.md` north-star #2).
The k8s equivalent is an image whose entrypoint pre-imports before fetching
the spec; this is the local backend's version of that warm start.

Closing stdin without a request is the shutdown signal (exit 0).
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    # Platform config (JAX_PLATFORMS, XLA_FLAGS device count) comes from the
    # spawn env — the pool keys workers by it, so this matches the job's.
    # On a TPU flavor this process HOLDS the chip from here on: no job that
    # does not claim this worker can start on it (docs/operator_guide.md).
    from .. import platform
    from ..obs import trace

    platform.enable_compile_cache()
    platform.devices()  # force backend init now, not at first trace

    # pre-import the whole training stack (flax/optax/orbax/models/data) —
    # JAX alone is under half the interpreter's import bill
    from . import checkpoint, cli, trainer  # noqa: F401
    # ``checkpoint`` leaves its library to a job's first save (~12 s on a
    # chip's host): a worker that waits for its job pays that here, ahead of it
    import orbax.checkpoint  # noqa: F401
    from ..data import loader, synthetic  # noqa: F401
    from ..models import multimodal  # noqa: F401

    ready = os.environ.get("FTC_WARM_READY_FILE")
    if ready:
        with open(ready, "w") as f:
            f.write("ready\n")

    # the pool may hold this process for minutes: on the start-up log the
    # wait is its own span, not an unexplained hole before ``trainer.build``
    with trace.STARTUP.span("startup.warm_wait"):
        line = sys.stdin.readline()
    # the sentinel's job is done once a request (or shutdown) arrives; the
    # worker owns its removal — the claim path's unlink is best-effort and
    # misses workers claimed before the file existed
    if ready:
        try:
            os.unlink(ready)
        except OSError:
            pass
    if not line.strip():
        return 0  # pool shutdown
    req = json.loads(line)

    # per-job env (trace identity: FTC_TRACE_ID / FTC_ATTEMPT) arrives with
    # the request — this process was spawned before the job existed, so the
    # usual spawn-env channel cannot carry it
    for k, v in (req.get("env") or {}).items():
        os.environ[k] = str(v)

    fd = os.open(req["log"], os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    os.close(fd)
    if req.get("cwd"):
        os.chdir(req["cwd"])

    from .cli import main as cli_main

    return cli_main(["--spec", req["spec"]])


if __name__ == "__main__":
    raise SystemExit(main())
