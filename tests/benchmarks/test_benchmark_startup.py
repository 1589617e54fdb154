"""ISSUE 38's benchmark side: the reducer that reads the program's start-up
log, the five per-layer metrics under ``setup_s`` — and what three tests of
``test_benchmark_falcon_h1.py`` and one of ``test_benchmark_mla_dsa_moe.py``
held of the manifest's 34 entries, of the 39
(``tests/conftest.py::SUPERSEDED``)."""

import json
import math
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import test_benchmark_falcon_h1 as accepted  # noqa: E402
import test_benchmark_mla_dsa_moe as sparse  # noqa: E402

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from finetune_controller_tpu.obs import trace  # noqa: E402

FIXTURE = ROOT / "tests/benchmarks/fixtures/BENCHMARK.startup.json"
CELLS = accepted.MISTRAL + [accepted.JOYAI, accepted.GLM, accepted.CELL]
#: this PR's entries, appended: the first per-layer metrics under ``setup_s``
ADDED = ["setup.import_s", "setup.trainer_build_s", "setup.step_trace_s",
         "setup.step_load_s", "setup.other_programs_s"]
LAYER = "start-up obs/trace.py train/trainer.py"


# ---- the reducer on a hand-made log -------------------------------------------------

def _span(name, start_s, end_s, **attrs):
    return trace.make_span(name, "", start_ns=int(start_s * 1e9),
                           end_ns=int(end_s * 1e9), **attrs)


HAND_MADE = [
    _span("startup.backend", 9.0, 9.5, already_up=True),
    _span("compile", 12.0, 14.0, fun_name="jit(_threefry_seed)", step=False,
          trace_s=0.5, lower_s=0.25, backend_s=1.25, cache="miss", import_s=0.25),
    _span("trainer.build", 10.0, 30.0, import_s=6.0, compile_s=1.75),
    _span("compile", 31.0, 32.0, fun_name="jit(make)", step=False, trace_s=0.5,
          lower_s=0.25, backend_s=0.25, cache="hit", cache_load_s=0.125,
          import_s=0.0),
    _span("compile.small", 11.0, 33.0, step=False, count=40, trace_s=0.0625,
          lower_s=0.0625, backend_s=0.125, import_s=0.0),
    _span("compile", 40.0, 52.0, fun_name="jit(_train_step)", step=True,
          trace_s=7.0, lower_s=2.0, backend_s=3.0, cache="hit",
          cache_load_s=2.5, import_s=1.5),
    _span("trainer.first_step", 39.0, 53.0, step_programs=1),
    _span("startup", 0.0, 53.0, anchor="process", import_s=20.0,
          import_by_package={"jax": 12.0, "orbax": 8.0}),
]


def _reduce(metric, monkeypatch, log=HAND_MADE, setup_s=60.0):
    m = Manifest()
    spec = m.layer_metric(metric)
    monkeypatch.setattr(trace, "STARTUP", types.SimpleNamespace(spans=log))
    run = types.SimpleNamespace(end_to_end={"setup_s": setup_s})
    return m.reducer(spec["reducer"])(run, **spec["args"])


@pytest.mark.parametrize("metric,seconds", [
    ("setup.import_s", 20.0),                            # the root's counter
    ("setup.trainer_build_s", 20.0 - 6.0 - 1.75),        # the constructor's own
    ("setup.step_trace_s", 7.0 + 2.0 - 1.5),             # the step's Python
    ("setup.step_load_s", 3.0),                          # the step's executable
    ("setup.other_programs_s", 2.0 - 0.25 + 1.0 + 0.25),  # every other program
])
def test_reducer_reads_each_cause_from_a_hand_made_log(metric, seconds,
                                                       monkeypatch, capsys):
    assert _reduce(metric, monkeypatch) == pytest.approx(seconds)
    err = capsys.readouterr().err
    if metric == "setup.other_programs_s":
        # 2 programs with a span of their own and 40 folded into one, the
        # longest first, each with its name and what the cache did
        assert "42 program(s)" in err
        assert err.index("jit(_threefry_seed) 2.00 s (miss)") \
            < err.index("jit(make) 1.00 s (hit)")
    if metric == "setup.step_load_s":
        assert "1 program(s)" in err and "jit(_train_step) 12.00 s (hit)" in err


def test_the_five_are_disjoint_a_second_is_counted_once(monkeypatch):
    total = sum(_reduce(m, monkeypatch) for m in ADDED)
    # imports 20 + constructor 12.25 + the step's 7.5 + 3 + the others' 3
    assert total == pytest.approx(45.75)
    assert total < 53.0     # the root's own seconds


def test_reducer_reads_nothing_where_the_program_keeps_no_log(monkeypatch):
    """The parent's ``obs/trace.py`` has no ``STARTUP``: every one of the five
    is left out of its line, nothing raises."""
    for metric in ADDED:
        assert _reduce(metric, monkeypatch, log=[]) is None
    m = Manifest()
    spec = m.layer_metric("setup.import_s")
    monkeypatch.delattr(trace, "STARTUP")
    run = types.SimpleNamespace(end_to_end={"setup_s": 60.0})
    assert m.reducer(spec["reducer"])(run, **spec["args"]) is None


def test_reducer_exits_where_the_spans_exceed_the_set_up(monkeypatch):
    with pytest.raises(SystemExit, match="counted twice"):
        _reduce("setup.import_s", monkeypatch, setup_s=19.0)


# ---- the manifest of 39 entries ---------------------------------------------------

@pytest.mark.parametrize("path", [None, FIXTURE], ids=["BENCHMARK.json", "fixture"])
def test_manifest_with_the_new_entries_has_no_problems(path):
    assert Manifest(path).problems() == []


@pytest.mark.parametrize("metric", ADDED)
def test_manifest_registers_and_loads_every_start_up_metric(metric):
    m = Manifest()
    entry, spec = m.per_layer[metric], m.layer_metric(metric)
    assert entry["moves"] == spec["moves"] == "setup_s"
    assert entry["workloads"] == CELLS
    assert all(metric in m.cell_per_layer(cell) for cell in CELLS)
    assert (entry["unit"], entry["better"], entry["layer"]) == ("s", "lower", LAYER)
    assert entry["source"] == spec["source"] == (
        "program_counter" if metric == "setup.import_s" else "program_span")
    assert spec["reducer"] == "program_startup_stat"
    assert callable(m.reducer(spec["reducer"]))


def test_the_real_manifest_has_its_five_cells_and_five_more_metrics_in_each():
    """What ``test_the_real_manifest_has_its_five_cells_and_no_metric_by_
    default`` held, of the 39 entries: the accepted cells report what they
    reported and the five under ``setup_s`` besides, every accepted entry
    lists the cells it listed."""
    m = Manifest()
    assert list(m.workloads) == CELLS
    for cell in accepted.MISTRAL:
        assert m.workload(cell)["driver"] == "train"
        assert m.cell_end_to_end(cell) == ["train_tokens_per_s_chip", "setup_s"]
        assert len(m.cell_per_layer(cell)) == 16 + len(ADDED)
    assert len(m.cell_per_layer(accepted.JOYAI)) == len(accepted.NEUTRAL) + 8 + 5
    assert len(m.cell_per_layer(accepted.GLM)) == (
        len(accepted.NEUTRAL) + 5 + len(accepted.GLM_ALONE) + 5)
    assert set(m.cell_per_layer(accepted.CELL)) == (
        accepted.NEUTRAL | accepted.DENSE_FLASH | accepted.SCANNED
        | set(accepted.ADDED) | set(ADDED))
    for entry in m.raw["per_layer"]:
        assert entry["workloads"] == (
            CELLS if entry["name"] in ADDED
            else accepted._cells_of(entry["name"])), entry["name"]
        assert entry["moves"] == (
            "setup_s" if entry["name"] in ADDED else "train_tokens_per_s_chip")
    assert m.end_to_end["train_tokens_per_s_chip"]["workloads"] == CELLS
    assert "workloads" not in m.end_to_end["setup_s"]
    assert m.raw["run_seconds"] == 45 and all(
        w["chips"] == 1 for w in m.raw["workloads"])


def test_expert_cells_report_the_neutral_metrics_their_own_and_the_start_up_five():
    """What ``test_cells_report_the_neutral_metrics_and_their_own_and_no_
    count_that_overstates`` held of the two expert cells, with the five under
    ``setup_s`` that every cell reports now: still no metric whose counts
    would read dense attention or eight whole experts a token there."""
    m = Manifest()
    assert set(m.cell_per_layer(accepted.JOYAI)) == (
        sparse.NEUTRAL | sparse.BOTH_EXPERT | sparse.JOYAI_ALONE | set(ADDED))
    for name in sparse.JOYAI_ALONE | {"mla.proj_matmul_roofline"}:
        assert m.layer_metric(name)["args"]["counts"] == "mla_moe"
    for name in sparse.JOYAI_ALONE:
        assert m.per_layer[name]["workloads"] == [accepted.JOYAI]
    assert m.cell_end_to_end(accepted.GLM) == ["train_tokens_per_s_chip", "setup_s"]
    assert set(m.cell_per_layer(accepted.GLM)) == (
        sparse.NEUTRAL | sparse.BOTH_EXPERT | set(sparse.ADDED) | set(ADDED))
    assert not set(m.cell_per_layer(accepted.GLM)) & (
        sparse.DENSE_LLAMA | sparse.JOYAI_ALONE)
    for name in ("dsa.index_scores_roofline", "dsa.sparse_attention_roofline",
                 "moe.held_experts_roofline", "trainer.mfu_selected_pct"):
        assert m.layer_metric(name)["args"]["counts"] == "mla_dsa_moe"
        assert m.per_layer[name]["workloads"] == [accepted.GLM]
    # none of the five reads a model's sizes: no ``counts`` module
    for name in ADDED:
        assert "counts" not in m.layer_metric(name)["args"]


def test_the_accepted_entries_stand_first_and_the_start_up_ones_last():
    names = [m["name"] for m in Manifest().raw["per_layer"]]
    assert names == accepted.ACCEPTED + accepted.ADDED + ADDED
    assert [c["name"] for c in Manifest().raw["configs"]] == [
        "mistral-7b-qlora", "joyai-llm-flash-lora", "glm-5.2-lora",
        "falcon-h1-34b-lora"]


def test_the_superseded_pins_are_sixteen_and_each_has_its_replacement():
    """``tests/conftest.py`` skips a pin only beside the test that holds what
    it held: the twelve of the manifests of two, three and four cells, and
    four of the five-cell one's 34 entries (held here)."""
    import conftest

    assert len(conftest.SUPERSEDED) == 16
    here = "tests/benchmarks/test_benchmark_startup.py::"
    held_here = 0
    for pin, (_, held_by) in conftest.SUPERSEDED.items():
        path, name = pin.split("::")
        assert f"def {name}(" in (ROOT / path).read_text()
        by_path, by_name = held_by.split("::")
        assert f"def {by_name}(" in (ROOT / by_path).read_text()
        if held_by.startswith(here):
            assert path in ("tests/benchmarks/test_benchmark_falcon_h1.py",
                            "tests/benchmarks/test_benchmark_mla_dsa_moe.py")
            assert callable(globals()[by_name])
            held_here += 1
    assert held_here == 4


# ---- end to end off the chip ---------------------------------------------------

def test_traced_tiny_run_prints_the_five_each_below_its_set_up(monkeypatch, capsys):
    """The tiny cell through the one entry with ``--trace 1``: the five are
    read in-process from the program's log, finite, each below the run's
    ``setup_s`` and their sum below it too.  A tier-1 worker's own log closed
    long ago, so the run gets a log of its own, as a fresh process has."""
    seen = {}
    find = Manifest.reducer

    def reducer(self, name):
        reduce = find(self, name)

        def spy(run, **args):
            seen["setup_s"] = run.end_to_end["setup_s"]
            return reduce(run, **args)
        return spy

    monkeypatch.setattr(Manifest, "reducer", reducer)
    log = trace.StartupLog(from_process_start=False).open()
    monkeypatch.setattr(trace, "STARTUP", log)
    try:
        runner.main(["--workload", "tiny-qlora.train-tiny", "--seed",
                     str(2**31 + 38), "--seconds", "0.5", "--trace", "1"],
                    manifest_path=FIXTURE, allow_cpu=True)
    finally:
        log.shutdown()
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert list(line["metrics"]) == ADDED
    values = [line["metrics"][m]["value"] for m in ADDED]
    assert all(math.isfinite(v) and 0 <= v < seen["setup_s"] for v in values)
    assert sum(values) < seen["setup_s"]
    assert all(line["metrics"][m]["unit"] == "s" for m in ADDED)
    # the step compiled, and said so
    assert line["metrics"]["setup.step_trace_s"]["value"] > 0
    assert line["metrics"]["setup.step_load_s"]["value"] > 0
    assert "jit(_train_step)" in err and "start-up: " in err
