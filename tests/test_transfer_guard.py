"""analysis/transfer_guard.py: the runtime device↔host sync guard.

Unit layer: window semantics (clean dispatch passes, implicit host→device
transfers abort, the ``jax.device_get`` trap works on EVERY backend
including this CPU box, warn mode observes without aborting, the first
call per label is compile-exempt).  Integration layer: the trainer's
jitted step and the serve engine's decode window run CLEAN under
``raise`` (zero trips on the default paths), and the ``FTC_FAULT_TRANSFER``
chaos hand — a real ``jax.device_get`` injected INSIDE the window — aborts
both, which is exactly the abort contract of the benchmark's timed windows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from finetune_controller_tpu.analysis.transfer_guard import (
    TransferGuard,
    TransferGuardError,
)


@pytest.fixture()
def add_one():
    fn = jax.jit(lambda x: x + 1)
    fn(jnp.arange(4.0))  # warm so windows never see the compile
    return fn


# ---------------------------------------------------------------------------
# window semantics
# ---------------------------------------------------------------------------


def test_clean_dispatch_passes_and_counts_zero(add_one):
    guard = TransferGuard("raise", skip_first=False)
    x = jnp.arange(4.0)
    for _ in range(3):
        with guard.window("step"):
            y = add_one(x)
    assert float(y[0]) == 1.0
    assert guard.trips == 0


def test_implicit_host_to_device_transfer_aborts(add_one):
    guard = TransferGuard("raise", skip_first=False)
    with pytest.raises(TransferGuardError, match="transfer"):
        with guard.window("step"):
            add_one(np.arange(4.0))  # np leaf at the jit boundary
    assert guard.trips == 1


def test_device_get_trap_fires_inside_window_only(add_one):
    guard = TransferGuard("raise", skip_first=False)
    x = jnp.arange(4.0)
    jax.device_get(x)  # outside any window: fine
    with pytest.raises(TransferGuardError, match="device_get"):
        with guard.window("step"):
            jax.device_get(x)
    assert guard.trips == 1
    jax.device_get(x)  # and fine again after the window


def test_trap_is_thread_local(add_one):
    """Another thread's jax.device_get during a window must NOT trip the
    guard — the serve engine steps in worker threads while the rest of the
    process uses jax freely."""
    import threading

    guard = TransferGuard("raise", skip_first=False)
    x = jnp.arange(4.0)
    errors = []

    def other_thread():
        try:
            jax.device_get(x)
        except BaseException as exc:  # pragma: no cover - the failure case
            errors.append(exc)

    with guard.window("step"):
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert errors == []
    assert guard.trips == 0


def test_first_call_per_label_is_compile_exempt(add_one):
    guard = TransferGuard("raise")  # skip_first defaults on
    with guard.window("step"):
        jax.device_get(jnp.arange(4.0))  # exempt: compile-time transfers
    with pytest.raises(TransferGuardError):
        with guard.window("step"):
            jax.device_get(jnp.arange(4.0))


def test_warn_mode_observes_without_aborting(add_one, caplog):
    guard = TransferGuard("warn", skip_first=False)
    x = jnp.arange(4.0)
    with guard.window("step"):
        jax.device_get(x)
        jax.device_get(x)
    assert guard.trips == 2  # counted...
    # ...and the dispatch completed — warn mode never raises


def test_nested_window_restores_outer(add_one):
    outer, inner = TransferGuard("raise", skip_first=False), \
        TransferGuard("raise", skip_first=False)
    x = jnp.arange(4.0)
    with outer.window("o"):
        with inner.window("i"):
            pass
        with pytest.raises(TransferGuardError):
            jax.device_get(x)  # the OUTER guard is active again
    assert outer.trips == 1 and inner.trips == 0


def test_from_env_parsing(monkeypatch):
    monkeypatch.delenv("FTC_TRANSFER_GUARD", raising=False)
    assert TransferGuard.from_env() is None
    monkeypatch.setenv("FTC_TRANSFER_GUARD", "off")
    assert TransferGuard.from_env() is None
    monkeypatch.setenv("FTC_TRANSFER_GUARD", "warn")
    assert TransferGuard.from_env().action == "warn"
    monkeypatch.setenv("FTC_TRANSFER_GUARD", "1")
    assert TransferGuard.from_env().action == "raise"
    with pytest.raises(ValueError):
        TransferGuard("explode")


def test_wrap_preserves_lower_for_aot(add_one):
    guard = TransferGuard("raise")
    wrapped = guard.wrap(add_one, "step")
    assert hasattr(wrapped, "lower")
    lowered = wrapped.lower(jnp.arange(4.0))
    assert lowered is not None


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------


def _tiny_trainer(tmp_path, **cfg_kw):
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.parallel import MeshSpec
    from finetune_controller_tpu.train import Trainer, TrainConfig

    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    mesh = MeshSpec(dp=1).build(jax.devices()[:1])
    train_cfg = TrainConfig(
        mode="lora", total_steps=4, batch_size=4, seq_len=16,
        log_every=2, checkpoint_every=1000, **cfg_kw,
    )
    return Trainer(model_cfg, train_cfg, mesh=mesh), model_cfg


def test_trainer_step_clean_under_raise(tmp_path):
    from finetune_controller_tpu.data import synthetic_batches

    trainer, model_cfg = _tiny_trainer(tmp_path, transfer_guard="raise")
    batches = synthetic_batches(4, 16, model_cfg.vocab_size, task="increment")
    trainer.fit(batches, str(tmp_path))
    assert trainer._transfer_guard is not None
    assert trainer._transfer_guard.trips == 0


def test_trainer_injected_device_get_aborts_the_run(tmp_path, monkeypatch):
    from finetune_controller_tpu.data import synthetic_batches

    monkeypatch.setenv("FTC_FAULT_TRANSFER", "1")
    trainer, model_cfg = _tiny_trainer(tmp_path, transfer_guard="raise")
    batches = synthetic_batches(4, 16, model_cfg.vocab_size, task="increment")
    with pytest.raises(TransferGuardError, match="device_get"):
        trainer.fit(batches, str(tmp_path))


def test_trainer_guard_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("FTC_TRANSFER_GUARD", raising=False)
    trainer, _ = _tiny_trainer(tmp_path)
    assert trainer._transfer_guard is None


def test_trainer_guard_inherits_env(tmp_path, monkeypatch):
    monkeypatch.setenv("FTC_TRANSFER_GUARD", "warn")
    trainer, _ = _tiny_trainer(tmp_path)
    assert trainer._transfer_guard is not None
    assert trainer._transfer_guard.action == "warn"


# ---------------------------------------------------------------------------
# serve-engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_model():
    from finetune_controller_tpu.models import PRESETS, LoRAConfig
    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    model = LlamaForCausalLM(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4), jnp.int32)
    )
    return model, variables


def _engine(serve_model, monkeypatch, *, fault: bool):
    from finetune_controller_tpu.serve.engine import BatchEngine, EngineConfig

    monkeypatch.setenv("FTC_TRANSFER_GUARD", "raise")
    if fault:
        monkeypatch.setenv("FTC_FAULT_TRANSFER", "1")
    model, variables = serve_model
    return BatchEngine(
        model, variables,
        EngineConfig(slots=2, prompt_buckets=(8,), max_new_tokens=8),
    )


def test_engine_decode_clean_under_raise(serve_model, monkeypatch):
    from finetune_controller_tpu.serve.engine import GenRequest

    engine = _engine(serve_model, monkeypatch, fault=False)
    results = engine.run([
        GenRequest(request_id="a", tokens=[1, 2, 3], max_new_tokens=6),
        GenRequest(request_id="b", tokens=[4, 5], max_new_tokens=6),
    ])
    assert {len(r.generated) for r in results.values()} == {6}
    assert engine._transfer_guard is not None
    assert engine._transfer_guard.trips == 0


def test_engine_injected_device_get_aborts_decode(serve_model, monkeypatch):
    from finetune_controller_tpu.serve.engine import GenRequest

    engine = _engine(serve_model, monkeypatch, fault=True)
    with pytest.raises(TransferGuardError, match="decode"):
        engine.run([GenRequest(request_id="c", tokens=[1, 2, 3],
                               max_new_tokens=6)])
    assert engine._transfer_guard.trips == 1
