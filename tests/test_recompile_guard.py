"""Runtime recompilation guard (analysis/recompile_guard.py): signature
counting, warn/raise policies, and the trainer integration behind
``TrainConfig.recompile_budget``."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.analysis.recompile_guard import (
    RecompileBudgetExceeded,
    RecompileGuard,
    signature_of,
)


def test_signature_distinguishes_shape_dtype_and_scalars():
    a = np.zeros((4, 8), np.float32)
    assert signature_of(a) == signature_of(np.ones((4, 8), np.float32))
    assert signature_of(a) != signature_of(np.zeros((4, 9), np.float32))
    assert signature_of(a) != signature_of(a.astype(np.int32))
    # jit traces Python scalars as weak-typed arrays: a varying VALUE does
    # not recompile (must not count), but a varying TYPE does
    assert signature_of(a, 1) == signature_of(a, 2)
    assert signature_of(a, 1) != signature_of(a, 1.0)
    # non-numeric leaves only reach jit as static args — value-keyed
    assert signature_of(a, "relu") != signature_of(a, "gelu")
    assert signature_of(x=a) != signature_of(y=a)


def test_stable_fn_stays_within_budget():
    guard = RecompileGuard(1, on_excess="raise")
    fn = guard.wrap(jax.jit(lambda x: x * 2), label="double")
    for i in range(5):
        out = fn(jnp.full((8,), i, jnp.float32))
    assert float(out[0]) == 8.0
    assert guard.compilations == 1


def test_shape_unstable_fn_detected_and_raises():
    """The acceptance-criteria case: an intentionally shape-unstable jitted
    fn (a new sequence length every call — the padding bug this guard
    exists to catch) blows the budget."""
    guard = RecompileGuard(2, on_excess="raise")
    fn = guard.wrap(jax.jit(lambda x: x.sum()), label="unstable")
    fn(jnp.zeros((4,)))
    fn(jnp.zeros((5,)))  # second shape: still within budget
    with pytest.raises(RecompileBudgetExceeded) as err:
        fn(jnp.zeros((6,)))
    assert "3 distinct jit compilations" in str(err.value)
    assert "unstable" in str(err.value)


def test_warn_mode_logs_once_and_keeps_running(caplog):
    guard = RecompileGuard(1, on_excess="warn")
    fn = guard.wrap(jax.jit(lambda x: x + 1), label="warned")
    with caplog.at_level(logging.WARNING,
                         logger="finetune_controller_tpu.analysis.recompile_guard"):
        for n in range(2, 6):
            fn(jnp.zeros((n,)))
    warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warnings) == 1  # one warning, not one per extra compile
    assert guard.compilations == 4


def test_budget_spans_labels():
    guard = RecompileGuard(2, on_excess="raise")
    f = guard.wrap(jax.jit(lambda x: x), label="a")
    g = guard.wrap(jax.jit(lambda x: -x), label="b")
    f(jnp.zeros((2,)))
    g(jnp.zeros((2,)))
    with pytest.raises(RecompileBudgetExceeded):
        g(jnp.zeros((3,)))
    assert guard.counts() == {"a": 1, "b": 2}


def test_guard_validates_config():
    with pytest.raises(ValueError):
        RecompileGuard(0)
    with pytest.raises(ValueError):
        RecompileGuard(1, on_excess="explode")


def test_trainer_threads_guard_behind_config_flag(devices8):
    from finetune_controller_tpu.data import synthetic_batches
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.parallel import MeshSpec
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    train_cfg = TrainConfig(
        mode="lora", total_steps=4, batch_size=8, seq_len=16,
        recompile_budget=1, recompile_action="raise", prefetch=0,
    )
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build(devices8)
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state = trainer.init_state()
    batches = synthetic_batches(8, 16, model_cfg.vocab_size, task="increment")
    # same batch structure every step: exactly one compile, budget holds
    for _ in range(3):
        state, _ = trainer.step(state, next(batches))
    assert trainer._recompile_guard.compilations == 1

    # a shape-unstable batch stream (seq_len drifts) must trip the guard
    short = {k: np.asarray(v)[:, :8] for k, v in next(batches).items()}
    with pytest.raises(RecompileBudgetExceeded):
        trainer.step(state, short)


def test_a_forced_second_signature_names_the_program_and_its_seconds():
    """The process's start-up log hears every compile by name, long after it
    has closed: the guard quotes what the step's program cost the last time,
    which is what the refused signature would have paid again."""
    import re

    from finetune_controller_tpu.obs import trace

    def forced_second_signature(x):
        return jnp.tanh(x).sum()

    guard = RecompileGuard(1, on_excess="raise")
    f = guard.wrap(jax.jit(forced_second_signature), label="step")
    f(jnp.zeros((4,)))
    heard = trace.STARTUP.programs["jit(forced_second_signature)"]
    assert heard["count"] == 1 and heard["cache"] in ("hit", "miss", "off")
    with pytest.raises(RecompileBudgetExceeded) as e:
        f(jnp.zeros((5,)))
    said = str(e.value)
    assert "jit(forced_second_signature) has compiled 1 time(s)" in said
    cost = heard["trace_s"] + heard["lower_s"] + heard["backend_s"]
    assert f"the last in {cost:.2f} s" in said
    assert re.search(r"trace \d+\.\d\d, lower \d+\.\d\d, backend \d+\.\d\d, "
                     r"cache (hit|miss|off)", said)
    # a function the log never heard of is quoted as before: no name, no cost
    quiet = RecompileGuard(1, on_excess="raise")
    with pytest.raises(RecompileBudgetExceeded) as e:
        quiet.check("a", ("sig", 1))
        quiet.check("a", ("sig", 2))
    assert "has compiled" not in str(e.value)


def test_trainer_guard_off_by_default(devices8):
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.parallel import MeshSpec
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    trainer = Trainer(
        model_cfg,
        TrainConfig(total_steps=1, batch_size=8, seq_len=16),
        mesh=MeshSpec(dp=2, fsdp=2, tp=2).build(devices8),
    )
    assert trainer._recompile_guard is None
