"""The prefs/ subsystem (ISSUE 8): DPO loss math, the DPO trainer, the
rollout buffer, the actor/learner loop, and gang scheduling semantics.

Loss-math unit tests are the satellite checklist verbatim: a hand-computed
tiny-logit example, beta monotonicity, masked-logprob parity with
``next_token_loss``'s reductions, and gradient-flows-only-through-policy.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from finetune_controller_tpu.data.preference import synthetic_preference_batches
from finetune_controller_tpu.models.llama import PRESETS
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.prefs.actor import increment_prompts, increment_reward
from finetune_controller_tpu.prefs.dpo_trainer import DPOTrainer
from finetune_controller_tpu.prefs.losses import (
    dpo_loss,
    masked_sequence_logprobs,
)
from finetune_controller_tpu.prefs.rollout_buffer import (
    PreferencePair,
    RolloutBuffer,
)
from finetune_controller_tpu.train.losses import next_token_loss
from finetune_controller_tpu.train.trainer import TrainConfig


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def test_dpo_loss_hand_computed():
    """B=1 with known logprobs: margin and loss match the closed form."""
    pc, pr = jnp.asarray([-1.0]), jnp.asarray([-2.0])
    rc, rr = jnp.asarray([-1.5]), jnp.asarray([-1.8])
    beta = 0.5
    # margin = beta * ((pc - rc) - (pr - rr)) = 0.5 * (0.5 - (-0.2)) = 0.35
    loss, metrics = dpo_loss(pc, pr, rc, rr, beta)
    assert math.isclose(float(metrics["reward_margin"]), 0.35, abs_tol=1e-6)
    expected = math.log(1.0 + math.exp(-0.35))
    assert math.isclose(float(loss), expected, rel_tol=1e-6)
    assert float(metrics["dpo_accuracy"]) == 1.0
    assert math.isclose(float(metrics["reward_chosen"]), 0.25, abs_tol=1e-6)
    assert math.isclose(float(metrics["reward_rejected"]), -0.1, abs_tol=1e-6)


def test_dpo_loss_tiny_logits_end_to_end():
    """Full pipeline on a hand-built (1, 3, 2) logit tensor.

    Uniform logits everywhere, one masked target per sequence ⇒ each
    per-sequence logprob is log(0.5); with policy == reference the margin is
    exactly 0 and the loss is log 2.
    """
    logits = jnp.zeros((1, 3, 2))
    tokens = jnp.asarray([[0, 1, 0]])
    mask = jnp.asarray([[0.0, 1.0, 0.0]])
    lp = masked_sequence_logprobs(logits, tokens, mask)
    assert math.isclose(float(lp[0]), math.log(0.5), rel_tol=1e-6)
    loss, metrics = dpo_loss(lp, lp, lp, lp, beta=0.3)
    assert math.isclose(float(loss), math.log(2.0), rel_tol=1e-6)
    assert float(metrics["reward_margin"]) == 0.0


def test_beta_monotonicity():
    """For a positive raw margin, larger beta ⇒ larger reward margin and
    smaller loss (the sigmoid sharpens); accuracy is beta-invariant."""
    pc, pr = jnp.asarray([-1.0, -1.2]), jnp.asarray([-2.0, -2.5])
    rc, rr = jnp.asarray([-1.5, -1.4]), jnp.asarray([-1.8, -2.0])
    prev_loss, prev_margin = None, None
    for beta in (0.1, 0.5, 2.0):
        loss, metrics = dpo_loss(pc, pr, rc, rr, beta)
        if prev_loss is not None:
            assert float(loss) < prev_loss
            assert float(metrics["reward_margin"]) > prev_margin
        assert float(metrics["dpo_accuracy"]) == 1.0
        prev_loss, prev_margin = float(loss), float(metrics["reward_margin"])


def test_masked_logprob_parity_with_next_token_loss():
    """-sum(per-seq masked logprobs) / mask_count == next_token_loss."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 12, 32)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 32, (4, 12)), jnp.int32)
    mask = jnp.asarray((rng.random((4, 12)) > 0.4), jnp.float32)
    loss, _ = next_token_loss(logits, tokens, mask)
    lp = masked_sequence_logprobs(logits, tokens, mask)
    denom = float(mask[:, 1:].sum())
    assert math.isclose(float(-lp.sum() / denom), float(loss), rel_tol=1e-5)


def test_gradient_flows_only_through_policy():
    """The reference side is stop-gradiented: d loss / d ref_lp == 0, while
    the policy side carries gradient."""
    pc, pr = jnp.asarray([-1.0]), jnp.asarray([-2.0])
    rc, rr = jnp.asarray([-1.5]), jnp.asarray([-1.8])

    def wrt_ref(rc_, rr_):
        return dpo_loss(pc, pr, rc_, rr_, 0.5)[0]

    def wrt_policy(pc_, pr_):
        return dpo_loss(pc_, pr_, rc, rr, 0.5)[0]

    g_rc, g_rr = jax.grad(wrt_ref, argnums=(0, 1))(rc, rr)
    assert float(jnp.abs(g_rc).sum()) == 0.0
    assert float(jnp.abs(g_rr).sum()) == 0.0
    g_pc, g_pr = jax.grad(wrt_policy, argnums=(0, 1))(pc, pr)
    assert float(jnp.abs(g_pc).sum()) > 0.0
    assert float(jnp.abs(g_pr).sum()) > 0.0


# ---------------------------------------------------------------------------
# DPO trainer
# ---------------------------------------------------------------------------


def _tiny_dpo_trainer(**overrides):
    cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    kw = dict(task="dpo", dpo_beta=0.2, batch_size=4, seq_len=16,
              total_steps=20, warmup_steps=2, learning_rate=1e-3,
              log_every=10**9, checkpoint_every=10**9, prefetch=0,
              heartbeat_interval_s=0)
    kw.update(overrides)
    return DPOTrainer(cfg, TrainConfig(**kw)), cfg


def test_dpo_trainer_margin_increases_and_ref_grad_free():
    trainer, cfg = _tiny_dpo_trainer(learning_rate=5e-3, total_steps=25)
    state = trainer.init_state()
    frozen_before = jax.tree.map(np.asarray, jax.device_get(
        dict(state.frozen)["params"]))
    batches = synthetic_preference_batches(4, 16, cfg.vocab_size, seed=0)
    margins = []
    for _ in range(25):
        state, metrics = trainer.step(state, next(batches))
        margins.append(float(metrics["reward_margin"]))
        assert "dpo_accuracy" in metrics and "accuracy" in metrics
    assert margins[-1] > margins[0] + 0.05, margins
    # the frozen reference never moved (stop-gradient + frozen collection)
    frozen_after = jax.tree.map(np.asarray, jax.device_get(
        dict(state.frozen)["params"]))
    jax.tree.map(np.testing.assert_array_equal, frozen_before, frozen_after)


def test_dpo_heldout_accuracy_after_training():
    """Eighty steps on the seeded synthetic preference set rank held-out
    pairs (a disjoint seed region, read through the job's own ``evaluate``)
    correctly at least seven times in ten, with the reward margin finite and
    higher in the last quarter of the run than in the first."""
    trainer, cfg = _tiny_dpo_trainer(
        batch_size=8, seq_len=32, total_steps=80, eval_steps=8,
        recompile_budget=4, recompile_action="raise")
    state = trainer.init_state()
    batches = synthetic_preference_batches(8, 32, cfg.vocab_size, seed=0)
    margins = []
    for _ in range(80):
        state, metrics = trainer.step(state, next(batches))
        margins.append(float(metrics["reward_margin"]))
    assert np.all(np.isfinite(margins))
    assert np.mean(margins[-20:]) > np.mean(margins[:20])
    held_out = synthetic_preference_batches(8, 32, cfg.vocab_size, seed=100_003)
    accuracy = float(trainer.evaluate(state, held_out)["eval_dpo_accuracy"])
    assert accuracy >= 0.7, accuracy


def test_dpo_trainer_restrictions():
    cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    with pytest.raises(ValueError, match="mode='lora'"):
        DPOTrainer(cfg, TrainConfig(task="dpo", mode="full"))
    with pytest.raises(ValueError, match="dpo_beta"):
        DPOTrainer(cfg, TrainConfig(task="dpo", dpo_beta=0.0))
    moe = PRESETS["tiny-moe-test"].replace(lora=LoRAConfig(rank=4))
    with pytest.raises(ValueError, match="MoE"):
        DPOTrainer(moe, TrainConfig(task="dpo"))


@pytest.mark.slow
def test_dpo_fit_checkpoints_and_resumes(tmp_path):
    """The full SFT lifecycle machinery under the DPO objective: metrics CSV
    carries reward_margin/dpo_accuracy, checkpoints commit, and a resumed
    fit continues step-continuous."""
    import csv

    trainer, cfg = _tiny_dpo_trainer(total_steps=6, log_every=2,
                                     checkpoint_every=2, eval_every=2,
                                     eval_steps=2)
    art = str(tmp_path / "art")
    batches = synthetic_preference_batches(4, 16, cfg.vocab_size, seed=0)
    evals = synthetic_preference_batches(4, 16, cfg.vocab_size, seed=100_003)
    trainer.fit(batches, art, resume=True, eval_batches=evals)
    with open(f"{art}/metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert [int(float(r["step"])) for r in rows] == [2, 4, 6]
    for col in ("reward_margin", "dpo_accuracy", "eval_reward_margin",
                "eval_dpo_accuracy"):
        assert col in rows[0], sorted(rows[0])
        assert rows[-1][col] != ""
    # resume: a fresh trainer continues from the last committed step
    trainer2, _ = _tiny_dpo_trainer(total_steps=8, log_every=2,
                                    checkpoint_every=2)
    batches2 = synthetic_preference_batches(4, 16, cfg.vocab_size, seed=0)
    trainer2.fit(batches2, art, resume=True)
    with open(f"{art}/metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert [int(float(r["step"])) for r in rows] == [2, 4, 6, 8]


# ---------------------------------------------------------------------------
# rollout buffer
# ---------------------------------------------------------------------------


def _pair(version, tag=0):
    return PreferencePair(prompt=(1, 2, tag), chosen=(3, 4), rejected=(5, 6),
                          version=version)


def test_rollout_buffer_bounded_fifo():
    buf = RolloutBuffer(capacity=3, seed=0)
    for i in range(5):
        buf.push(_pair(version=i, tag=i))
    assert buf.depth == 3
    assert min(p.version for p in buf._pairs) == 2  # oldest two dropped
    assert buf.pushed_total == 5


def test_rollout_buffer_staleness_eviction_and_metric():
    buf = RolloutBuffer(capacity=10, seed=0)
    for v in (0, 0, 5, 10):
        buf.push(_pair(version=v))
    dropped = buf.evict_below(5, watermark=10)
    assert dropped == 2 and buf.depth == 2
    assert buf.evicted_stale_total == 2
    assert buf.staleness == 5  # oldest surviving pair is 5 behind watermark
    assert buf.stats()["rollout_staleness"] == 5


def test_rollout_buffer_deterministic_sampling():
    def build():
        buf = RolloutBuffer(capacity=8, seed=42)
        for i in range(6):
            buf.push(_pair(version=i, tag=i))
        return buf

    a, b = build(), build()
    for _ in range(3):
        ba, bb = a.sample_batch(4, 8), b.sample_batch(4, 8)
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k])
    with pytest.raises(ValueError, match="empty"):
        RolloutBuffer(capacity=2).sample_batch(1, 8)


# ---------------------------------------------------------------------------
# actor / learner
# ---------------------------------------------------------------------------


def test_increment_reward():
    assert increment_reward([5], [6, 7, 8], 256) == 1.0
    assert increment_reward([5], [6, 9, 10], 256) == pytest.approx(2 / 3)
    assert increment_reward([255], [0], 256) == 1.0  # wraps mod vocab
    assert increment_reward([5], [], 256) == 0.0


def test_increment_prompts_deterministic():
    a = [next(increment_prompts(16, 256, seed=3)) for _ in range(1)]
    b = [next(increment_prompts(16, 256, seed=3)) for _ in range(1)]
    assert a == b
    p = a[0]
    assert len(p) == 8 and p[1] == (p[0] + 1) % 256


@pytest.mark.slow
def test_actor_reloads_committed_checkpoint(tmp_path):
    """The actor picks up a committed checkpoint, swaps weights with ZERO new
    compiles, and its pair stream is seed-deterministic."""
    from finetune_controller_tpu.prefs.learner import (
        RolloutConfig,
        build_rlhf_loop,
    )

    trainer, cfg = _tiny_dpo_trainer(task="rlhf", batch_size=2, seq_len=16,
                                     total_steps=2, checkpoint_every=1,
                                     log_every=1)
    art = str(tmp_path / "art")
    stream, actor, buffer = build_rlhf_loop(
        trainer, art,
        rollout=RolloutConfig(pairs_per_round=4, min_fill=4,
                              buffer_capacity=32, max_new_tokens=4,
                              slots=2, temperature=0.9),
    )
    assert actor.version == 0 and not actor.maybe_reload()
    first = next(stream)  # fills the buffer from the step-0 policy
    assert set(first) == {"chosen_tokens", "chosen_mask",
                          "rejected_tokens", "rejected_mask"}
    compiles_after_first = actor.compilations
    # commit checkpoints through the learner and observe the reload: the
    # step-2 pull sees the step-1 commit (the final step-2 commit has no
    # later pull to be observed by)
    trainer.fit(stream, art, resume=True)
    assert actor.reloads == 1 and actor.version == 1
    assert actor.compilations == compiles_after_first  # reload ≠ recompile
    assert actor.compilations <= actor.compile_budget
    now = actor.maybe_reload()  # a later round picks up the final commit
    assert now and actor.version == 2


@pytest.mark.slow
def test_rlhf_loop_generate_commit_reload_cycle(tmp_path):
    """ISSUE 8 acceptance smoke (in-process): the actor generates from
    checkpoint N, the learner commits N+1, and the actor reloads N+1 on the
    next rollout round — with the reward margin rising and the engine inside
    its compile budget."""
    import csv

    from finetune_controller_tpu.prefs.learner import (
        RolloutConfig,
        build_rlhf_loop,
    )

    trainer, cfg = _tiny_dpo_trainer(task="rlhf", batch_size=4, seq_len=32,
                                     total_steps=15, checkpoint_every=5,
                                     log_every=5)
    art = str(tmp_path / "art")
    stream, actor, buffer = build_rlhf_loop(
        trainer, art,
        rollout=RolloutConfig(pairs_per_round=6, min_fill=6,
                              buffer_capacity=64, max_new_tokens=8,
                              slots=4, temperature=0.9),
    )
    trainer.fit(stream, art, resume=True)
    with open(f"{art}/metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    # the row at step k*5 trained on rollouts from the checkpoint committed
    # at (k-1)*5 — a one-round reload lag, never more
    assert [int(float(r["actor_version"])) for r in rows] == [0, 5, 10]
    assert actor.reloads == 2 and actor.version == 10
    assert actor.compilations <= actor.compile_budget
    margins = [float(r["reward_margin"]) for r in rows]
    assert margins[-1] > margins[0], margins
    assert float(rows[-1]["rollout_buffer_depth"]) >= 6
    assert buffer.pushed_total > 0


@pytest.mark.slow
def test_rlhf_job_through_the_cli(tmp_path):
    """`train/cli.py` end to end for task=rlhf: the spec class renders the
    rollout section, run_job selects the DPO learner, wires the actor, and
    the artifacts carry rollout metrics + checkpoints + done.txt."""
    import csv
    import os

    from finetune_controller_tpu.controller.examples import (
        RLHFArguments,
        TinyRLHFTest,
    )
    from finetune_controller_tpu.train.cli import run_job

    spec = TinyRLHFTest(training_arguments=RLHFArguments(
        total_steps=4, warmup_steps=1, batch_size=2, seq_len=16, lora_rank=2,
        log_every=2, checkpoint_every=2, beta=0.2,
        rollout_pairs_per_round=4, rollout_min_fill=4,
        rollout_max_new_tokens=4, rollout_slots=2,
    ))
    art = str(tmp_path / "artifacts")
    # the backend normally renders the mesh from the device flavor; pin a
    # 1-device mesh here so the in-process run ignores the pytest host's
    # virtual device count
    trainer_spec = spec.build_trainer_spec("rlhf-cli-1", art,
                                           mesh={"fsdp": 1})
    assert trainer_spec["training"]["task"] == "rlhf"
    assert trainer_spec["training"]["dpo_beta"] == 0.2
    assert trainer_spec["rollout"]["pairs_per_round"] == 4
    assert "extra_arguments" not in trainer_spec
    run_job(trainer_spec)
    assert os.path.exists(f"{art}/done.txt")
    with open(f"{art}/metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert rows and "reward_margin" in rows[0]
    assert "rollout_buffer_depth" in rows[0]
    assert any(p.startswith("step_") for p in os.listdir(f"{art}/checkpoints"))


# ---------------------------------------------------------------------------
# gang scheduling (sched/ min_slices floor)
# ---------------------------------------------------------------------------


def _gang_sched(quota=2):
    from conftest import one_chip_catalog
    from finetune_controller_tpu.sched import FairShareScheduler

    return FairShareScheduler(one_chip_catalog(quota=quota),
                              {"prod": 4.0, "batch": 1.0})


def test_gang_never_admitted_shrunk():
    """Elastic admission starts ordinary multi-slice jobs shrunk on free
    chips — but an atomic gang waits for its FULL size."""
    sched = _gang_sched(quota=2)
    sched.submit("occupier", "chip-1", 1, queue="batch")
    sched.try_admit()
    # a plain 2-slice workload admits shrunk onto the free chip...
    sched.submit("elastic", "chip-1", 2, queue="prod")
    admitted = sched.try_admit()
    assert [w.job_id for w in admitted] == ["elastic"]
    assert sched.workload("elastic").num_slices == 1  # shrunk
    sched.release("elastic")
    # ...the same shape submitted as a gang stays pending
    sched.submit("gang", "chip-1", 2, queue="prod", min_slices=2)
    assert sched.try_admit() == []
    assert sched.workload("gang").admitted is False


def test_gang_victim_evicted_never_shrunk():
    """Preemption against a gang escalates straight to eviction: a partial
    gang cannot run, so there is nothing to shrink to."""
    sched = _gang_sched(quota=2)
    sched.submit("gang", "chip-1", 2, queue="batch", priority="low",
                 min_slices=2)
    assert [w.job_id for w in sched.try_admit()] == ["gang"]
    sched.submit("urgent", "chip-1", 1, queue="prod", priority="high")
    sched.try_admit()
    decisions = sched.take_preemptions()
    assert [d.kind for d in decisions] == ["evict"]
    assert decisions[0].job_id == "gang"


def test_non_gang_victim_still_shrinks():
    """Control: the identical scenario without the gang floor SHRINKS the
    victim (the PR-7 behavior is unchanged for ordinary jobs)."""
    sched = _gang_sched(quota=2)
    sched.submit("elastic", "chip-1", 2, queue="batch", priority="low")
    sched.try_admit()
    sched.submit("urgent", "chip-1", 1, queue="prod", priority="high")
    sched.try_admit()
    decisions = sched.take_preemptions()
    assert [d.kind for d in decisions] == ["shrink"]


def test_rlhf_spec_is_atomic_gang():
    from finetune_controller_tpu.controller.examples import TinyRLHFTest
    from finetune_controller_tpu.controller.specs import TrainingTask

    assert TinyRLHFTest.atomic_gang is True
    assert TinyRLHFTest.default_num_slices == 2
    assert TinyRLHFTest.task is TrainingTask.RLHF


def test_dpo_spec_renders_preference_dataset():
    from finetune_controller_tpu.controller.examples import (
        DPOArguments,
        TinyDPOTest,
    )

    spec = TinyDPOTest(training_arguments=DPOArguments(beta=0.3))
    rendered = spec.build_trainer_spec("dpo-1", "/tmp/a")
    assert rendered["training"]["task"] == "dpo"
    assert rendered["training"]["dpo_beta"] == 0.3
    assert rendered["dataset"] == {"synthetic": {"task": "preference"}}
    assert "rollout" not in rendered
    assert "extra_arguments" not in rendered
