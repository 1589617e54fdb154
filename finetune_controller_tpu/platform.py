"""Process-level JAX set-up shared by every entry point that starts a backend.

The installed JAX honours ``JAX_PLATFORMS`` by itself, so no platform
resolution lives here: a process told ``JAX_PLATFORMS=tpu`` fails at backend
start-up when it finds no chip, and one told ``cpu`` never looks for one.
What the entry points (``train/cli.py``, ``train/warm_worker.py``,
``transport/worker.py``, ``benchmarks/run.py``, ``tests/conftest.py``) do
share is where compiled programs are cached and how a process says which
device it landed on.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

#: the checkout (or install root) this package was imported from
REPO_ROOT = Path(__file__).resolve().parents[1]


def compile_cache_dir() -> str:
    """Where this process keeps JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.cache/xla``.
    Always a fixed path — the directory is part of the cache key, so one
    that moved (a temp dir, a pid, a timestamp) would never hit."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO_ROOT / ".cache" / "xla"
    )


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and return
    its directory.  Call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and no
    other directory is set in code; without it the cache goes to the fixed
    path in the checkout, so every process of one checkout (trainer, serve
    worker, benchmark, tests and the subprocesses they spawn) shares one cache.
    Programs that took under half a second to compile are cached too unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise — the test
    suite is made of such programs.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def devices() -> list:
    """``jax.devices()``.  The program's first call of it while its start-up
    log is open is the log's ``startup.backend`` span: the backend's start,
    or — ``already_up`` — nothing, where the host process had started it."""
    import jax

    from .obs import trace

    log = trace.STARTUP
    if log.closed or log.seconds("startup.backend") is not None:
        return jax.devices()
    bridge = sys.modules.get("jax._src.xla_bridge")
    with log.span("startup.backend") as span:
        up = bool(bridge and bridge.backends_are_initialized())
        found = jax.devices()
        span["attributes"].update(
            platform=found[0].platform, kind=found[0].device_kind,
            count=len(found), already_up=up)
    return found


def device_report() -> dict:
    """The device this process runs on, as JAX reports it — what a parent
    that must stay off JAX (the API server, ``chip_smoke.py``) reads back
    from its trainer and serve-worker children.  Starts the backend."""
    found = devices()
    return {
        "platform": found[0].platform,
        "kind": found[0].device_kind,
        "count": len(found),
    }
