"""A tiny rehearsal of each driver through the Python entry (the CLI itself
refuses a CPU), the lower-precision control, and the timed path broken
underneath — ``correct`` must come out false."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness import compare  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

TINY = ROOT / "tests/benchmarks/fixtures/BENCHMARK.tiny.json"
CUT = ROOT / "tests/benchmarks/fixtures/BENCHMARK.cut.json"
SEED = 2**31 + 17


def drive(cell, trace=0, seconds=1.0, seed=SEED, manifest=TINY):
    return runner.main(["--workload", cell, "--seed", str(seed), "--seconds",
                        str(seconds), "--trace", str(trace)],
                       manifest_path=manifest, allow_cpu=True)


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,metric", [
    ("tiny-qlora.train-tiny", "train_tokens_per_s_chip"),
    ("tiny-qwen-lora.train-tiny4", "train_tokens_per_s_chip"),
    ("tiny-qlora.serve-tiny", "ttft_p90_ms"),
])
def test_cell_runs_and_prints_the_contract_line(cell, metric, capsys):
    drive(cell, seconds=2.0 if "serve" in cell else 0.5)
    line = last_line(capsys)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    # every number compared stands beside its limit, last in the line
    assert all(set(row) == {"value", "limit"} for row in line["compared"].values())
    assert "no_compile_in_window" in line["compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == set(Manifest(TINY).cell_end_to_end(cell))
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert line["device"]["count"] == Manifest(TINY).workloads[cell]["chips"]


@pytest.mark.parametrize("cell,has,lacks", [
    ("tiny-qlora.train-tiny", ["trainer.dispatch_ms", "tiny.steps_count"],
     ["engine.decode_step_ms"]),
    ("tiny-qlora.serve-tiny", ["engine.decode_step_ms", "batcher.lanes_busy_mean"],
     ["trainer.dispatch_ms"]),
])
def test_traced_run_reports_the_cells_per_layer_metrics(cell, has, lacks, capsys):
    drive(cell, trace=1, seconds=2.0 if "serve" in cell else 0.5)
    metrics = last_line(capsys)["metrics"]
    for name in has:
        assert metrics[name]["value"] >= 0, name
    for name in lacks:
        assert name not in metrics


def test_a_cut_cell_of_another_program_runs_through_the_one_train_driver(
        monkeypatch, capsys):
    """ISSUE 26: program, reference and configuration come by files and
    entries alone (``fixtures/programs``, ``fixtures/reference``,
    ``configs/tiny-cut.json``); the window and ``correct`` are
    ``drivers/train.py``'s.  The program's layers are unrolled, so every
    adapter leaf lies outside the scanned stack and is compared as ONE
    entry, with no layer axis read into it."""
    seen = []
    real = compare.worst_leaf_gap
    monkeypatch.setattr(compare, "worst_leaf_gap",
                        lambda prog, ref: seen.append((prog, ref)) or real(prog, ref))
    drive("tiny-cut.train-tiny", seconds=0.3, manifest=CUT)
    line = last_line(capsys)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["compared"]) == {
        "losses_finite", "no_compile_in_window", "loss_step1_gap",
        "loss_step2_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    assert len(seen) == 2
    for prog, ref in seen:
        assert set(prog) == set(ref) and len(ref) == 4 * 7 * 2
        assert "layer_3/mlp/down_proj/lora_b" in ref
        assert not any("[" in name or name.startswith("blocks") for name in ref)


def test_the_cut_cells_step_stuck_is_not_correct(monkeypatch, capsys):
    """The unstacked leaves are really compared: with the adapters held
    where they were, their change reads 1 against the reference's."""
    from finetune_controller_tpu.train.trainer import Trainer

    real = Trainer.step
    monkeypatch.setattr(
        Trainer, "step", lambda self, state, batch: (
            lambda new, m: (new.replace(trainable=state.trainable), m))(
                *real(self, state, batch)))
    drive("tiny-cut.train-tiny", seconds=0.3, manifest=CUT)
    line = last_line(capsys)
    assert line["correct"] is False
    assert line["compared"]["param_change_norm_gap"]["value"] == pytest.approx(1.0, abs=0.05)


def test_compared_numbers_are_the_last_lines_on_standard_error(capsys):
    drive("tiny-qlora.train-tiny", seconds=0.3)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert [t.split(":")[0] for t in tail] == [
        f"compared {name}" for name in line["compared"]]
    assert all("(limit " in t for t in tail)


def test_cli_refuses_a_cpu():
    with pytest.raises(SystemExit) as e:
        runner.main(["--workload", "tiny-qlora.train-tiny", "--seed", "1",
                     "--seconds", "1"], manifest_path=TINY)
    assert e.value.code not in (0, None)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    """Drives the rest of a run with the trainer's step broken underneath."""
    from finetune_controller_tpu.train.trainer import Trainer

    real = Trainer.step

    def stuck(self, state, batch):
        new, metrics = real(self, state, batch)
        return new.replace(trainable=state.trainable), metrics

    monkeypatch.setattr(
        Trainer, "_get_step_jit",
        lambda self, batch, _orig=Trainer._get_step_jit: _undonated(self, batch, _orig))
    monkeypatch.setattr(Trainer, "step", stuck)
    drive("tiny-qlora.train-tiny", seconds=0.3)
    out = capsys.readouterr().out
    assert "param_change_norm_gap" in out and "NOT CORRECT" in out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False


def _undonated(self, batch, orig):
    return orig(self, batch)   # CPU steps do not donate; the old state lives


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    from finetune_controller_tpu.serve.engine import BatchEngine

    real = BatchEngine._record

    def altered(self, slot, tok):
        return real(self, slot, (tok + 1) % 256)

    monkeypatch.setattr(BatchEngine, "_record", altered)
    drive("tiny-qlora.serve-tiny", seconds=2.0)
    out = capsys.readouterr().out
    assert "served_token_widest_logit_gap" in out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False


@pytest.mark.parametrize("seed", [SEED, 5, 77])
def test_train_control_in_lower_precision_fails_a_limit(seed):
    """The control: the reference put in the program's place, computed in
    scaled float8 — the step below the bf16 the configuration states.  Its
    gradient is a real reading (a bare cast underflows the cotangent to
    zero, which reads exactly 1 and says nothing)."""
    from benchmarks.harness import data
    from benchmarks.harness.drivers.train import judge
    from benchmarks.reference import model as ref_model

    m = Manifest(TINY)
    conf, wl = m.config("tiny-qlora"), m.workload("tiny-qlora.train-tiny")
    reference_numbers = m.reference(conf).reference_numbers
    gen = data.increment_batches(wl["batch"], wl["seq"], conf["vocab_size"], seed)
    tokens = [next(gen)["tokens"] for _ in range(wl["reference_steps"])]
    ref = reference_numbers(conf, wl, seed, tokens)
    control = reference_numbers(conf, wl, seed, tokens, q=ref_model.to_fp8,
                                precision="default")
    cmp = compare.Comparison()
    judge(cmp, wl["limits"], control, ref)
    assert not cmp.correct
    grad = next(r for r in cmp.rows if r[0] == "first_grad_norm_gap")
    assert wl["limits"]["first_grad_norm_gap"] < grad[1] < 0.5
    sound = compare.Comparison()
    judge(sound, wl["limits"], ref, ref)
    assert sound.correct


def test_serve_control_in_lower_precision_reads_a_wide_gap():
    from benchmarks.harness.drivers.serve import served_gaps
    from benchmarks.reference import model as ref_model

    m = Manifest(TINY)
    conf, wl = m.config("tiny-qlora"), m.workload("tiny-qlora.serve-tiny")
    rng = np.random.default_rng(3)
    samples = [(rng.integers(0, 256, 24).tolist(), rng.integers(0, 256, 8).tolist())
               for _ in range(12)]
    gaps = served_gaps(conf, SEED, samples, control_q=ref_model.to_fp8)
    assert gaps["tokens"] == 96
    limits = wl["limits"]
    assert (gaps["mean"] > limits["served_token_mean_logit_gap"]
            or gaps["widest"] > limits["served_token_widest_logit_gap"]), gaps
    print(gaps)


def test_layer_norms_index_the_scanned_stack_only():
    flat = {"blocks/attn/q_proj/lora_a": np.array([[3.0, 4.0], [0.0, 1.0]]),
            "dense_0/mlp/up_proj/lora_b": np.array([[3.0, 4.0], [0.0, 12.0]]),
            "lm_head/lora_a": np.array([2.0])}
    assert compare.layer_norms(flat) == {
        "blocks/attn/q_proj/lora_a[0]": 5.0, "blocks/attn/q_proj/lora_a[1]": 1.0,
        "dense_0/mlp/up_proj/lora_b": 13.0, "lm_head/lora_a": 2.0}


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-9}, ref) == pytest.approx(0.1)
    # an all-but-zero leaf is held against the median leaf, not itself
    assert compare.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5}, ref) == pytest.approx(0.5)
