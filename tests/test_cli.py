import json
import os
import subprocess
import sys

from finetune_controller_tpu.train import cli


def _spec(tmp_path, **training):
    return {
        "job_id": "test-job",
        "model": {"preset": "tiny-test", "lora": {"rank": 4}},
        "training": {
            "mode": "lora", "total_steps": 4, "batch_size": 4, "seq_len": 16,
            "log_every": 2, "checkpoint_every": 100, **training,
        },
        "mesh": {"dp": 1, "fsdp": 1, "tp": 1},
        "dataset": {"synthetic": {"task": "increment"}},
        "artifacts_dir": str(tmp_path / "artifacts"),
    }


def test_run_job_in_process(tmp_path):
    spec = _spec(tmp_path)
    cli.run_job(spec)
    art = tmp_path / "artifacts"
    assert (art / "done.txt").exists()
    assert (art / "metrics.csv").exists()
    assert (art / "resolved_config.json").exists()
    header = (art / "metrics.csv").read_text().splitlines()[0]
    assert "loss" in header and "tokens_per_sec" in header


def test_cli_subprocess(tmp_path):
    """The exact launch path the local training backend uses."""
    spec = _spec(tmp_path)
    spec_path = tmp_path / "job.json"
    spec_path.write_text(json.dumps(spec))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "finetune_controller_tpu.train.cli", "--spec", str(spec_path)],
        capture_output=True, text=True, timeout=300, env=env, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert (tmp_path / "artifacts" / "done.txt").exists()


def test_unconsumed_extra_arguments_rejected(tmp_path):
    """A user argument the spec class never mapped must fail loudly, not be
    silently dropped (round-1 weak spot)."""
    spec = _spec(tmp_path)
    spec["extra_arguments"] = {"my_custom_knob": 3}
    try:
        cli.run_job(spec)
        raise AssertionError("should have raised")
    except ValueError as e:
        assert "my_custom_knob" in str(e)


def test_bad_spec_rejected(tmp_path):
    spec = _spec(tmp_path)
    spec["training"]["bogus_field"] = 1
    try:
        cli.run_job(spec)
        raise AssertionError("should have raised")
    except ValueError as e:
        assert "bogus_field" in str(e)


def test_unknown_model_field_refused(tmp_path):
    """``model.overrides`` is outside input: a field ``LlamaConfig`` does not
    have (here a kernel knob that is gone) is refused by name."""
    import pytest

    spec = _spec(tmp_path)
    spec["model"]["overrides"] = {"flash_block_q": 256}
    with pytest.raises(TypeError, match="flash_block_q"):
        cli.run_job(spec)
    assert not (tmp_path / "artifacts" / "done.txt").exists()


def test_eval_loop_writes_heldout_metrics(tmp_path):
    """eval_every drives a held-out evaluation: eval columns ride on the
    train log rows at the eval cadence (dense rows — ragged cells would
    parse as NaN in the control plane's pandas reader)."""
    import csv

    spec = _spec(tmp_path, total_steps=4, eval_every=2)
    spec["training"]["eval_steps"] = 2
    cli.run_job(spec)
    rows = list(csv.DictReader(open(tmp_path / "artifacts" / "metrics.csv")))
    assert "eval_loss" in rows[0]
    eval_rows = [r for r in rows if r["eval_loss"]]
    assert len(eval_rows) == 2  # steps 2 and 4
    assert {r["step"] for r in eval_rows} == {"2", "4"}
    for r in eval_rows:
        assert float(r["eval_loss"]) > 0
        assert float(r["loss"]) > 0  # eval rides on a full train row


def test_eval_without_heldout_split_fails_loudly(tmp_path):
    spec = _spec(tmp_path, eval_every=2)
    spec["dataset"] = {"path": str(tmp_path / "train.jsonl")}
    (tmp_path / "train.jsonl").write_text('{"text": "hello world"}\n' * 8)
    import pytest

    with pytest.raises(ValueError, match="no eval split"):
        cli.run_job(spec)


def _run_generate(argv):
    """Invoke generate_cli.main, returning its one-line JSON output."""
    import io
    from contextlib import redirect_stdout

    from finetune_controller_tpu.models import generate_cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert generate_cli.main(argv) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_generate_cli_from_artifacts(tmp_path):
    """Post-finetune generation CLI: train a tiny job, then generate from
    its artifacts dir — the resume recipe (seeded init + latest checkpoint)
    plus both token-id and byte-prompt modes, greedy determinism across
    invocations."""
    spec = _spec(tmp_path, checkpoint_every=2)
    cli.run_job(spec)
    art = str(tmp_path / "artifacts")
    run = _run_generate

    out = run(["--artifacts", art, "--prompt-tokens", "5,6,7,8",
               "--max-new-tokens", "6"])
    assert out["checkpoint_step"] == 4
    assert len(out["new_tokens"]) == 6
    assert all(0 <= t < 256 for t in out["new_tokens"])
    assert out["text"] is None  # token-id mode: ids in, ids out

    # greedy is deterministic across fresh invocations
    again = run(["--artifacts", art, "--prompt-tokens", "5,6,7,8",
                 "--max-new-tokens", "6"])
    assert again["new_tokens"] == out["new_tokens"]

    # byte-prompt mode decodes text through the data pipeline's fallback
    out = run(["--artifacts", art, "--prompt", "abc", "--max-new-tokens", "4"])
    assert isinstance(out["text"], str)

    # guard rails: bad ids and missing checkpoint fail loudly
    import pytest

    with pytest.raises(SystemExit, match="out of range"):
        run(["--artifacts", art, "--prompt-tokens", "999999"])
    with pytest.raises(SystemExit, match="exactly one"):
        run(["--artifacts", art])


def test_generate_cli_uses_job_tokenizer(tmp_path):
    """--prompt must tokenize with the tokenizer the JOB trained with
    (dataset.tokenizer_file in resolved_config.json), not the byte
    fallback — and decode output through it."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {f"w{i}": i for i in range(16)}
    vocab["hello"] = 16
    vocab["[UNK]"] = 17
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    tok_file = tmp_path / "tok.json"
    tok_file.write_text(tok.to_str())

    spec = _spec(tmp_path, checkpoint_every=2)
    spec["dataset"]["tokenizer_file"] = str(tok_file)
    cli.run_job(spec)

    out = _run_generate(
        ["--artifacts", str(tmp_path / "artifacts"), "--prompt", "hello",
         "--max-new-tokens", "3"]
    )
    # "hello" is ONE WordLevel token (id 16), not 5 byte tokens
    assert out["prompt_tokens"] == 1
    # output decodes through the same tokenizer (all ids < vocab 256 decode
    # to either known words or empty; text must be a str, not null)
    assert isinstance(out["text"], str)


def test_generate_cli_mesh_fallback_and_full_mode(tmp_path, capsys):
    """Two resume-recipe edges: a job mesh this host can't form falls back
    to the default single-device mesh (with a note, not a crash), and
    mode='full' jobs skip the pretrained-base reload (the checkpoint holds
    every weight)."""
    spec = _spec(tmp_path, checkpoint_every=2, mode="full", learning_rate=1e-3)
    del spec["model"]["lora"]
    cli.run_job(spec)

    # rewrite the recorded spec: a mesh the conftest's 8 devices cannot form
    # (-> fallback note, not a crash) and a weights_dir that would crash if
    # the full-mode skip didn't apply
    art_spec = json.loads(
        (tmp_path / "artifacts" / "resolved_config.json").read_text()
    )
    art_spec["mesh"] = {"dp": 64}
    art_spec["model"]["weights_dir"] = str(tmp_path / "does-not-exist")
    (tmp_path / "artifacts" / "resolved_config.json").write_text(
        json.dumps(art_spec)
    )

    out = _run_generate(
        ["--artifacts", str(tmp_path / "artifacts"),
         "--prompt-tokens", "5,6,7", "--max-new-tokens", "2"]
    )
    assert len(out["new_tokens"]) == 2
    err = capsys.readouterr().err
    assert "job mesh" in err and "unavailable here" in err
