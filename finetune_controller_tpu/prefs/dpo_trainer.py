"""DPO on top of the LoRA SFT trainer — same machinery, different objective.

:class:`DPOTrainer` swaps the loss (``prefs/losses.py``) and nothing else:
sharded init, the jitted step with donation/grad-accum, checkpoint manifests,
elastic resume, preemption handling, heartbeats, and the metrics CSV all ride
``train/trainer.py`` unchanged.  The metrics rows gain ``reward_margin`` and
``dpo_accuracy`` (plus their ``eval_`` twins on the eval cadence).

The reference model is FREE here (docs/preference.md): in LoRA mode the
policy is base + adapter, so the reference forward is the frozen base with
the adapter branch disabled — a rank-0 twin of the model applied over the
``params`` collection only.  No second weight copy exists on device, and no
gradient path into the trainable tree exists on the reference side (tested).

Batch contract (``data/preference.py``)::

    {"chosen_tokens", "chosen_mask", "rejected_tokens", "rejected_mask"}

Chosen and rejected sequences are stacked into ONE ``(2B, S)`` forward per
model (policy and reference), so a DPO step costs two forwards of twice the
batch — not four forwards.
"""

from __future__ import annotations

import logging

import jax.numpy as jnp

from ..models.lora import LoRAConfig
from ..train.trainer import Trainer
from .losses import dpo_loss, masked_sequence_logprobs

logger = logging.getLogger(__name__)


class DPOTrainer(Trainer):
    """Preference-pair trainer (``TrainConfig.task == "dpo"``/``"rlhf"``).

    Restrictions (all checked at construction): LoRA mode only (the
    adapter-disabled reference trick is what makes the reference model free;
    full fine-tune would need a second frozen weight copy), dense text models
    (MoE capacity routing couples the stacked chosen/rejected rows; the
    multimodal prefix has no pair semantics), no pipeline parallelism.
    """

    def __init__(self, model_cfg, train_cfg, mesh=None, **kw):
        if train_cfg.mode != "lora":
            raise ValueError(
                "DPO requires mode='lora': the reference model is the "
                "adapter-disabled base, which only exists in LoRA mode"
            )
        if getattr(model_cfg, "n_experts", 0):
            raise ValueError("DPO does not support MoE configs")
        if getattr(model_cfg, "vision", None) is not None:
            raise ValueError("DPO supports text models only")
        if train_cfg.dpo_beta <= 0:
            raise ValueError(f"dpo_beta must be > 0, got {train_cfg.dpo_beta}")
        super().__init__(model_cfg, train_cfg, mesh=mesh, **kw)
        if self._pp > 1:
            raise ValueError("DPO does not support pipeline parallelism")
        #: the reference forward: the SAME architecture at LoRA rank 0 —
        #: its ``params`` tree is structurally identical to the policy's
        #: frozen base, so it applies over ``frozen["params"]`` directly
        self._ref_model = type(self.model)(
            cfg=model_cfg.replace(
                lora=LoRAConfig(
                    rank=0,
                    alpha=model_cfg.lora.alpha,
                    targets=model_cfg.lora.targets,
                )
            )
        )
        #: host-side metrics provider for the rlhf learner (rollout buffer
        #: depth/staleness, actor tok/s) — merged into every logged row
        self.rollout_stats_fn = None
        if train_cfg.task == "rlhf" \
                and not getattr(train_cfg, "rollout_workers", 0):
            # IN-PROCESS loop only.  The actor only sees COMMITTED
            # checkpoints; synchronous commits bound its policy lag
            # deterministically (one round), where an async save could land
            # arbitrarily many rollout rounds late
            self._blocking_checkpoints = True
            if train_cfg.prefetch:
                # the rollout stream RUNS the actor inside next(): a
                # background prefetch thread would interleave the serve
                # engine's decode steps with the learner's jitted steps and
                # read checkpoints concurrently with the blocking save —
                # enforce here so every caller (cli, harnesses) is
                # covered
                logger.info("rlhf task: forcing prefetch=0 (actor runs inline)")
                train_cfg.prefetch = 0
        # remote rollout workers (rollout_workers > 0) keep BOTH: actors
        # decode in their own processes, so prefetch threads never touch the
        # learner's engine, and async checkpoint commits are safe — the
        # plane pushes a policy only after latest_step() reports it durable.
        # That async overlap is the whole point of disaggregation
        # (docs/preference.md §Disaggregated rollouts).

    # ---- objective -------------------------------------------------------

    def _pair_logprobs(self, model, variables, batch, rngs=None):
        """(chosen_lp, rejected_lp), each (B,): one stacked (2B, S) forward."""
        b = batch["chosen_tokens"].shape[0]
        tokens = jnp.concatenate(
            [batch["chosen_tokens"], batch["rejected_tokens"]], axis=0
        )
        masks = jnp.concatenate(
            [batch["chosen_mask"], batch["rejected_mask"]], axis=0
        )
        logits = model.apply(
            variables, tokens,
            deterministic=rngs is None, rngs=rngs,
        )
        lp = masked_sequence_logprobs(logits, tokens, masks)
        return lp[:b], lp[b:]

    def _dpo_metrics(self, trainable, frozen, batch, dropout_rng=None):
        variables = self._assemble(frozen, trainable)
        rngs = (
            {"dropout": dropout_rng}
            if (self._use_dropout and dropout_rng is not None) else None
        )
        pc, pr = self._pair_logprobs(self.model, variables, batch, rngs=rngs)
        # adapter-disabled reference: frozen base only, always deterministic
        rc, rr = self._pair_logprobs(
            self._ref_model, {"params": frozen["params"]}, batch
        )
        loss, metrics = dpo_loss(pc, pr, rc, rr, self.cfg.dpo_beta)
        # fit()'s log line and the eval_* naming expect loss/accuracy keys;
        # accuracy IS the pair-ranking accuracy for a preference objective
        metrics["accuracy"] = metrics["dpo_accuracy"]
        metrics["policy_chosen_logprob"] = pc.mean()
        metrics["policy_rejected_logprob"] = pr.mean()
        return loss, metrics

    def _loss_fn(self, trainable, frozen, batch, dropout_rng):
        return self._dpo_metrics(trainable, frozen, batch, dropout_rng)

    def _eval_step(self, state, batch: dict):
        """Forward-only DPO metrics on held-out pairs (dropout off)."""
        _, metrics = self._dpo_metrics(state.trainable, state.frozen, batch)
        return metrics

    # ---- metrics plumbing ------------------------------------------------

    def _writer_extra_fields(self, eval_enabled: bool) -> tuple[str, ...]:
        fields = super()._writer_extra_fields(eval_enabled)
        if eval_enabled:
            fields += ("eval_reward_margin", "eval_dpo_accuracy")
        if self.rollout_stats_fn is not None:
            fields += (
                "rollout_buffer_depth", "rollout_staleness",
                "actor_tokens_per_sec", "actor_version",
            )
            if getattr(self.cfg, "rollout_workers", 0):
                fields += (
                    "rollout_workers_alive", "rollout_respawns_total",
                    "rollout_dup_pairs_total",
                )
        return fields

    def _row_extras(self) -> dict:
        if self.rollout_stats_fn is None:
            return {}
        return {k: float(v) for k, v in self.rollout_stats_fn().items()}
