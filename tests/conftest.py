"""Test harness: an 8-virtual-device CPU mesh so every parallelism strategy
(DP/FSDP/TP/SP) is exercised without TPU hardware — the CPU-simulation test
seam the reference lacked entirely (SURVEY.md §4).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=8".strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Persistent XLA compilation cache for the test suite: every run re-compiles
# the same tiny-model programs (train steps per remat policy, decode fills,
# pipeline stages ...), which dominates tier-1 wall-clock on a small CPU box.
# The program's own helper decides the directory (JAX_COMPILATION_CACHE_DIR,
# else <checkout>/.cache/xla), so the trainers and serve workers the tests
# spawn share it.  JAX_ENABLE_COMPILATION_CACHE=false turns it off everywhere
# when debugging compiler flags or suspecting a stale-cache artifact.
from finetune_controller_tpu.platform import enable_compile_cache  # noqa: E402

enable_compile_cache()

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


def run_async(coro):
    """Run a coroutine on a fresh, properly closed event loop."""
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def one_chip_catalog(quota: int = 2):
    """Single 1-chip CPU flavor catalog for backend/scheduler tests."""
    from finetune_controller_tpu.controller.devices import (
        DeviceCatalog,
        DeviceFlavor,
        FlavorQuota,
    )

    return DeviceCatalog(
        flavors=[DeviceFlavor(name="chip-1", generation="cpu", hosts=1,
                              chips_per_host=1, runtime="cpu", queue="q")],
        quotas=[FlavorQuota(flavor="chip-1", nominal_chips=quota)],
        default_flavor="chip-1",
    )


def tiny_job_spec(steps: int = 3):
    """Milliseconds-scale TinyTestLoRA spec for lifecycle tests."""
    from finetune_controller_tpu.controller.examples import (
        LoRASFTArguments,
        TinyTestLoRA,
    )

    return TinyTestLoRA(
        training_arguments=LoRASFTArguments(
            total_steps=steps, warmup_steps=1, batch_size=2, seq_len=16, lora_rank=2
        )
    )
