import jax
import numpy as np
import pytest

from finetune_controller_tpu.data import synthetic_batches
from finetune_controller_tpu.models import PRESETS, LoRAConfig
from finetune_controller_tpu.parallel import MeshSpec
from finetune_controller_tpu.train import Trainer, TrainConfig


def _tiny_cfg(rank=4):
    return PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=rank))


def test_lora_training_reduces_loss(devices8, tmp_path):
    model_cfg = _tiny_cfg()
    train_cfg = TrainConfig(
        mode="lora", learning_rate=2e-2, warmup_steps=2, total_steps=40,
        batch_size=8, seq_len=32, log_every=5, checkpoint_every=1000,
    )
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build(devices8)
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    batches = synthetic_batches(8, 32, model_cfg.vocab_size, task="increment")
    losses = []
    trainer.fit(
        batches, str(tmp_path), on_metrics=lambda s, m: losses.append(m["loss"])
    )
    assert losses[-1] < losses[0] * 0.7, f"loss did not drop: {losses}"
    assert (tmp_path / "metrics.csv").exists()


def test_full_finetune_mode(devices8, tmp_path):
    model_cfg = PRESETS["tiny-test"]  # no LoRA
    train_cfg = TrainConfig(
        mode="full", learning_rate=1e-3, warmup_steps=2, total_steps=10,
        batch_size=8, seq_len=16, log_every=5, checkpoint_every=1000,
    )
    mesh = MeshSpec(dp=1, fsdp=4, tp=2).build(devices8)
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    batches = synthetic_batches(8, 16, model_cfg.vocab_size, task="increment")
    losses = []
    trainer.fit(batches, str(tmp_path), on_metrics=lambda s, m: losses.append(m["loss"]))
    assert losses[-1] < losses[0]


def test_params_are_actually_sharded(devices8):
    model_cfg = _tiny_cfg()
    train_cfg = TrainConfig(total_steps=1, batch_size=8, seq_len=16)
    mesh = MeshSpec(dp=1, fsdp=2, tp=4).build(devices8)
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state = trainer.init_state()
    # a scanned attention kernel should be sharded over fsdp×tp
    kern = state.frozen["params"]["blocks"]["block"]["attn"]["q_proj"]["kernel"]
    assert len(kern.sharding.device_set) == 8
    shard_shape = kern.sharding.shard_shape(kern.shape)
    assert shard_shape[1] == kern.shape[1] // 2  # fsdp split on in-features
    assert shard_shape[2] == kern.shape[2] // 4  # tp split on out-features


def test_checkpoint_resume_continues(devices8, tmp_path):
    model_cfg = _tiny_cfg()
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build(devices8)
    batches = lambda: synthetic_batches(4, 16, model_cfg.vocab_size, task="increment")

    cfg1 = TrainConfig(
        mode="lora", total_steps=6, batch_size=4, seq_len=16,
        log_every=2, checkpoint_every=3,
    )
    t1 = Trainer(model_cfg, cfg1, mesh=mesh)
    state1 = t1.fit(batches(), str(tmp_path))
    assert int(state1.step) == 6

    # same artifacts dir, more steps → resumes from step 6
    cfg2 = TrainConfig(
        mode="lora", total_steps=9, batch_size=4, seq_len=16,
        log_every=2, checkpoint_every=3,
    )
    t2 = Trainer(model_cfg, cfg2, mesh=mesh)
    state2 = t2.fit(batches(), str(tmp_path))
    assert int(state2.step) == 9

    # restored trainable matched what was saved (step-6 ckpt still on disk)
    from finetune_controller_tpu.train.checkpoint import CheckpointManager

    ckpt = CheckpointManager(str(tmp_path / "checkpoints"))
    assert set(ckpt.all_steps()) >= {6, 9}


def test_profiler_trace_ships_with_artifacts(tmp_path):
    """SURVEY.md §5.1 gap: a jax.profiler trace window lands under
    {artifacts}/profile so the artifact sync ships it with the job."""
    model_cfg = _tiny_cfg()
    cfg = TrainConfig(
        mode="lora", total_steps=6, batch_size=2, seq_len=16,
        log_every=100, checkpoint_every=1000,
        profile_steps=2, profile_start_step=1,
    )
    trainer = Trainer(model_cfg, cfg)
    batches = synthetic_batches(2, 16, model_cfg.vocab_size)
    trainer.fit(batches, str(tmp_path), resume=False)
    profile_dir = tmp_path / "profile"
    traces = list(profile_dir.rglob("*.xplane.pb"))
    assert traces, f"no trace files under {profile_dir}"


def test_metrics_writer_resume_gains_columns(tmp_path):
    """A resumed run that enables eval mid-life rewrites the CSV under the
    union header instead of silently dropping the new columns."""
    import csv

    from finetune_controller_tpu.train.metrics import MetricsWriter

    w = MetricsWriter(str(tmp_path))
    w.write({"step": 1, "loss": 2.0})
    w.close()
    w2 = MetricsWriter(
        str(tmp_path), append=True, extra_fields=("eval_loss", "eval_accuracy")
    )
    w2.write({"step": 2, "loss": 1.5, "eval_loss": 1.8, "eval_accuracy": 0.4})
    w2.close()
    rows = list(csv.DictReader(open(tmp_path / "metrics.csv")))
    assert rows[0]["loss"] == "2.0" and rows[0]["eval_loss"] == ""
    assert rows[1]["eval_loss"] == "1.8" and rows[1]["eval_accuracy"] == "0.4"


def test_grad_accumulation_matches_unsplit_step(devices8):
    """grad_accum_steps=N on a sharded mesh produces (near-)identical
    parameter updates to the unsplit step on the same global batch, and the
    invalid configurations fail loudly at construction."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import pytest

    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.parallel.mesh import MeshSpec
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    cfg = PRESETS["tiny-test"].replace(
        lora=LoRAConfig(rank=4), dtype=jnp.float32
    )
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build(devices8)

    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32),
        "loss_mask": np.ones((8, 32), np.float32),
    }

    def one_step(accum):
        tc = TrainConfig(
            mode="lora", batch_size=8, seq_len=32, total_steps=1,
            learning_rate=0.01, warmup_steps=0, clip_norm=0.0,
            log_every=10**9, checkpoint_every=10**9, grad_accum_steps=accum,
        )
        tr = Trainer(cfg, tc, mesh=mesh)
        state = tr.init_state()
        state, metrics = tr.step(state, dict(batch))
        host = jax.tree.map(lambda x: np.asarray(x), state.trainable)
        return host, {k: float(v) for k, v in metrics.items()}

    t1, m1 = one_step(1)
    t4, m4 = one_step(2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), t1, t4
    )
    assert abs(m1["loss"] - m4["loss"]) < 1e-4
    assert m1["target_tokens"] == m4["target_tokens"] == 8 * 31

    with pytest.raises(ValueError, match="not divisible by"):
        Trainer(cfg, TrainConfig(mode="lora", batch_size=8, grad_accum_steps=3),
                mesh=mesh)
    with pytest.raises(ValueError, match="batch sharding"):
        Trainer(cfg, TrainConfig(mode="lora", batch_size=8, grad_accum_steps=8),
                mesh=mesh)


@pytest.mark.parametrize("impl,sp,want", [
    ("auto", 1, "xla"),        # the rule's own answer off the TPU
    ("pallas", 1, "pallas"),   # an explicit kernel is kept
    ("xla", 2, "ring"),        # an sp axis shards the sequence
])
def test_runtime_attrs_reports_the_traced_impl(
        devices8, monkeypatch, impl, sp, want):
    """The ``train-started`` event's ``attention_impl`` is the chooser's
    answer, and the step traces exactly that: every resolution
    ``causal_attention`` makes while the step is traced gives it.  With the
    flash kernels it also carries ``flash_causal_work_over_need``."""
    from finetune_controller_tpu.ops import attention
    from finetune_controller_tpu.ops.pallas.flash_attention import (
        causal_work_over_need,
    )

    traced = []
    resolve = attention.resolve_attention_impl

    def spy(*args, **kwargs):
        traced.append(resolve(*args, **kwargs))
        return traced[-1]

    # the trainer holds the function itself, so the spy sees only the
    # model's calls
    monkeypatch.setattr(attention, "resolve_attention_impl", spy)
    model_cfg = _tiny_cfg().replace(attention_impl=impl)
    train_cfg = TrainConfig(total_steps=1, batch_size=4, seq_len=16)
    mesh = MeshSpec(dp=1, fsdp=1, sp=sp).build(devices8[:sp])
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state = trainer.init_state()
    traced.clear()
    batch = next(synthetic_batches(4, 16, model_cfg.vocab_size, task="increment"))
    _, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert traced and set(traced) == {want}
    attrs = trainer._runtime_attrs()
    assert attrs["attention_impl"] == want
    # the flash kernels' static counter rides along exactly when they run
    if want == "pallas":
        assert attrs["flash_causal_work_over_need"] == causal_work_over_need(16)
    else:
        assert "flash_causal_work_over_need" not in attrs
