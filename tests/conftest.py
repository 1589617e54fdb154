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


#: the tests under ``tests/benchmarks/`` that pin a ``BENCHMARK.json`` a later
#: ``model_config`` PR appended to — three of PR 26's two cells (ISSUE 27),
#: five of PR 27's three (ISSUE 32), four of PR 32's four (ISSUE 36), four of
#: PR 36's 34 per-layer entries (ISSUE 38), six of PR 38's five cells
#: (ISSUE 40), five of PR 40's six (ISSUE 43), five of PR 43's seven
#: (ISSUE 49) — and that
#: only a ``benchmark`` PR may
#: edit (a PR of another kind changes no file the benchmark already has):
#: ``node id -> (why, the test that holds what it held)``.  The next
#: ``benchmark`` PR edits the thirty-two and deletes this table and the hook
#: under it (ROADMAP.md, B1)
SUPERSEDED = {
    "tests/benchmarks/test_benchmark_manifest.py::"
    "test_the_real_manifest_has_its_two_cells_and_no_metric_by_default": (
        "pins PR 26's two cells; ISSUE 27 adds a third",
        "tests/benchmarks/test_benchmark_mla_moe.py::"
        "test_the_real_manifest_has_its_three_cells_and_no_metric_by_default"),
    "tests/benchmarks/test_benchmark_scopes.py::"
    "test_manifest_registers_and_loads_every_new_metric": (
        "pins every PR 24 entry's cells to PR 26's two; ISSUE 27 appends its "
        "cell to the architecture-neutral ones",
        "tests/benchmarks/test_benchmark_mla_moe.py::"
        "test_manifest_registers_and_loads_every_accepted_metric"),
    "tests/benchmarks/test_benchmark_scopes.py::"
    "test_the_accepted_entries_stand_first_and_unchanged": (
        "pins the list's end; ISSUE 27 appends its entries",
        "tests/benchmarks/test_benchmark_mla_moe.py::"
        "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
    # ... and the five of ``test_benchmark_mla_moe.py`` that pin PR 27's
    # three-cell manifest, to which ISSUE 32 appends a fourth cell, six
    # per-layer entries and its cell's name in sixteen accepted ones
    **{"tests/benchmarks/test_benchmark_mla_moe.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_mla_dsa_moe.py::" + held_by)
       for pin, why, held_by in (
        ("test_the_real_manifest_has_its_three_cells_and_no_metric_by_default",
         "pins PR 27's three cells; ISSUE 32 adds a fourth",
         "test_the_real_manifest_has_its_four_cells_and_no_metric_by_default"),
        ("test_manifest_registers_and_loads_every_accepted_metric",
         "pins every entry's cells to PR 27's three; ISSUE 32 appends its cell "
         "to the neutral ones and to five of PR 27's",
         "test_manifest_registers_and_loads_every_accepted_metric"),
        ("test_the_accepted_entries_stand_first_and_the_new_ones_last",
         "pins the lists' ends; ISSUE 32 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
        ("test_cell_reports_the_neutral_metrics_and_its_own_and_no_dense_llama_count",
         "pins mla.proj_matmul_roofline to the JoyAI cell alone; the new cell "
         "reports it too",
         "test_cells_report_the_neutral_metrics_and_their_own_and_no_count_that_overstates"),
        ("test_the_superseded_pins_are_three_and_each_has_its_replacement_here",
         "pins this table's length at three",
         "test_the_superseded_pins_are_eight_and_each_has_its_replacement"),
    )},
    # ... and the four of ``test_benchmark_mla_dsa_moe.py`` that pin PR 32's
    # four-cell manifest, to which ISSUE 36 appends a fifth cell, four
    # per-layer entries and its cell's name in thirteen accepted ones
    **{"tests/benchmarks/test_benchmark_mla_dsa_moe.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_falcon_h1.py::" + held_by)
       for pin, why, held_by in (
        ("test_the_real_manifest_has_its_four_cells_and_no_metric_by_default",
         "pins PR 32's four cells; ISSUE 36 adds a fifth",
         "test_the_real_manifest_has_its_five_cells_and_no_metric_by_default"),
        ("test_manifest_registers_and_loads_every_accepted_metric",
         "pins every entry's cells to PR 32's four; ISSUE 36 appends its cell "
         "to the neutral ones, the dense flash roofline and the loop's plumbing",
         "test_manifest_registers_and_loads_every_accepted_metric"),
        ("test_the_accepted_entries_stand_first_and_the_new_ones_last",
         "pins the lists' ends; ISSUE 36 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
        ("test_the_superseded_pins_are_eight_and_each_has_its_replacement",
         "pins this table's length at eight",
         "test_the_superseded_pins_are_twelve_and_each_has_its_replacement"),
    )},
    # ... and the three of ``test_benchmark_falcon_h1.py`` that pin PR 36's 34
    # per-layer entries, to which ISSUE 38 appends the five under ``setup_s``
    # (every cell reports them: each cell's count of metrics grows by five)
    **{"tests/benchmarks/test_benchmark_falcon_h1.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_startup.py::" + held_by)
       for pin, why, held_by in (
        ("test_the_real_manifest_has_its_five_cells_and_no_metric_by_default",
         "pins each cell's count of per-layer metrics; ISSUE 38 adds five to "
         "every cell",
         "test_the_real_manifest_has_its_five_cells_and_five_more_metrics_in_each"),
        ("test_the_accepted_entries_stand_first_and_the_new_ones_last",
         "pins the list's end; ISSUE 38 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_start_up_ones_last"),
        ("test_the_superseded_pins_are_twelve_and_each_has_its_replacement",
         "pins this table's length at twelve",
         "test_the_superseded_pins_are_sixteen_and_each_has_its_replacement"),
    )},
    # ... and the two of ``test_benchmark_falcon_h1.py`` and four of
    # ``test_benchmark_startup.py`` that pin PR 38's five-cell manifest, to
    # which ISSUE 40 appends a sixth cell, a fifth configuration, three
    # per-layer entries and its cell's name in twenty-two accepted ones
    **{f"tests/benchmarks/test_benchmark_{file}.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_nemotron_h.py::" + held_by)
       for file, pin, why, held_by in (
        ("falcon_h1", "test_manifest_registers_and_loads_every_accepted_metric",
         "pins every entry's cells to PR 36's five; ISSUE 40 appends its cell "
         "to the neutral ones, the dense flash roofline, the loop's plumbing, "
         "the mixer's two shares and the expert layer's two",
         "test_manifest_registers_and_loads_every_accepted_metric"),
        ("falcon_h1", "test_the_new_cell_is_the_one_the_issue_names",
         "pins the mixer's two shares to the hybrid cell alone; the pattern "
         "cell reports them too",
         "test_the_hybrid_cell_is_still_the_one_issue_36_named"),
        ("startup", "test_manifest_registers_and_loads_every_start_up_metric",
         "pins the five start-up entries' cells to PR 38's five; the sixth "
         "cell reports them too",
         "test_manifest_registers_and_loads_every_start_up_metric"),
        ("startup",
         "test_the_real_manifest_has_its_five_cells_and_five_more_metrics_in_each",
         "pins PR 38's five cells; ISSUE 40 adds a sixth",
         "test_the_real_manifest_has_its_six_cells_and_no_metric_by_default"),
        ("startup",
         "test_the_accepted_entries_stand_first_and_the_start_up_ones_last",
         "pins the lists' ends; ISSUE 40 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
        ("startup",
         "test_the_superseded_pins_are_sixteen_and_each_has_its_replacement",
         "pins this table's length at sixteen",
         "test_the_superseded_pins_are_twenty_two_and_each_has_its_replacement"),
    )},
    # ... and the five of ``test_benchmark_nemotron_h.py`` that pin PR 40's
    # six-cell manifest, to which ISSUE 43 appends a seventh cell, a sixth
    # configuration, five per-layer entries and its cell's name in nineteen
    # accepted ones
    **{"tests/benchmarks/test_benchmark_nemotron_h.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_mimo_v2.py::" + held_by)
       for pin, why, held_by in (
        ("test_the_real_manifest_has_its_six_cells_and_no_metric_by_default",
         "pins PR 40's six cells; ISSUE 43 adds a seventh",
         "test_the_real_manifest_has_its_seven_cells_and_no_metric_by_default"),
        ("test_manifest_registers_and_loads_every_accepted_metric",
         "pins every entry's cells to PR 40's six; ISSUE 43 appends its cell "
         "to the neutral ones, the flash kernels' share, the loop's plumbing "
         "and the expert layer's two",
         "test_manifest_registers_and_loads_every_accepted_metric"),
        ("test_manifest_registers_and_loads_every_start_up_metric",
         "pins the five start-up entries' cells to PR 40's six; the seventh "
         "cell reports them too",
         "test_manifest_registers_and_loads_every_start_up_metric"),
        ("test_the_accepted_entries_stand_first_and_the_new_ones_last",
         "pins the lists' ends; ISSUE 43 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
        ("test_the_superseded_pins_are_twenty_two_and_each_has_its_replacement",
         "pins this table's length at twenty-two",
         "test_the_superseded_pins_are_twenty_seven_and_each_has_its_replacement"),
    )},
    # ... and the five of ``test_benchmark_mimo_v2.py`` that pin PR 43's
    # seven-cell manifest, to which ISSUE 49 appends an eighth cell, a seventh
    # configuration, seven per-layer entries and its cell's name in seventeen
    # accepted ones
    **{"tests/benchmarks/test_benchmark_mimo_v2.py::" + pin: (
        why, "tests/benchmarks/test_benchmark_minicpm_sala.py::" + held_by)
       for pin, why, held_by in (
        ("test_the_real_manifest_has_its_seven_cells_and_no_metric_by_default",
         "pins PR 43's seven cells; ISSUE 49 adds an eighth",
         "test_the_real_manifest_has_its_eight_cells_and_no_metric_by_default"),
        ("test_manifest_registers_and_loads_every_accepted_metric",
         "pins every entry's cells to PR 43's seven; ISSUE 49 appends its cell "
         "to the neutral ones, the flash kernels' share and the loop's plumbing",
         "test_manifest_registers_and_loads_every_accepted_metric"),
        ("test_manifest_registers_and_loads_every_start_up_metric",
         "pins the five start-up entries' cells to PR 43's seven; the eighth "
         "cell reports them too",
         "test_manifest_registers_and_loads_every_start_up_metric"),
        ("test_the_accepted_entries_stand_first_and_the_new_ones_last",
         "pins the lists' ends; ISSUE 49 appends its entries",
         "test_the_accepted_entries_stand_first_and_the_new_ones_last"),
        ("test_the_superseded_pins_are_twenty_seven_and_each_has_its_replacement",
         "pins this table's length at twenty-seven",
         "test_the_superseded_pins_are_thirty_two_and_each_has_its_replacement"),
    )},
    "tests/benchmarks/test_benchmark_mla_dsa_moe.py::"
    "test_cells_report_the_neutral_metrics_and_their_own_and_no_count_that_overstates": (
        "pins the two expert cells' sets of per-layer metrics; ISSUE 38 adds "
        "its five to every cell",
        "tests/benchmarks/test_benchmark_startup.py::"
        "test_expert_cells_report_the_neutral_metrics_their_own_and_the_start_up_five"),
}


def pytest_collection_modifyitems(config, items):
    """Skip a superseded pin ONLY in a session that also collected the test
    holding what it held: without its replacement (renamed, deleted, or left
    out of the run) the pin runs, and fails."""
    collected = {item.nodeid.split("[")[0] for item in items}
    for item in items:
        why, held_by = SUPERSEDED.get(item.nodeid.split("[")[0], (None, None))
        if held_by in collected:
            item.add_marker(pytest.mark.skip(
                reason=f"{why}; held by {held_by} until a benchmark PR "
                       "edits the file"))
