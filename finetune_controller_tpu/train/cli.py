"""Training entrypoint: ``python -m finetune_controller_tpu.train.cli --spec job.json``.

This is the process the control plane launches (locally as a subprocess, or
on-cluster as the container command of every TPU worker pod).  The JSON spec
is the contract between the planes — the deployer renders it, this module
consumes it.  On completion it touches ``done.txt`` in the artifacts dir, the
same completion signal the reference used to stop its S3-sync sidecar
(reference ``app/jobs/kubeflow/PyTorchJobDeployer.py:30-32``).

Spec schema (all sections optional except artifacts_dir):

    {
      "job_id": "...",
      "model":    {"preset": "tiny-test", "overrides": {...}, "lora": {"rank": 8}},
      "training": {... TrainConfig fields ...},
      "mesh":     {"dp": 1, "fsdp": -1, "tp": 1, "sp": 1, "ep": 1, "pp": 1},
      "dataset":  {"path": "...", "tokenizer_file": null, "eval_path": "..."}
                  | {"synthetic": {"task": "increment"}},
      "artifacts_dir": "/data/artifacts"
    }

With ``training.eval_every > 0`` a held-out stream is evaluated on that
cadence: ``dataset.eval_path`` when given, otherwise a disjoint synthetic
stream (offset seed), and eval_loss/eval_accuracy columns join metrics.csv.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys

logger = logging.getLogger(__name__)


def build_model_config(spec: dict):
    from ..models.llama import PRESETS
    from ..models.lora import LoRAConfig
    from ..models.multimodal import MM_PRESETS

    model_spec = spec.get("model", {})
    preset = model_spec.get("preset", "tiny-test")
    if preset in PRESETS:
        cfg = PRESETS[preset]
    elif preset in MM_PRESETS:
        cfg = MM_PRESETS[preset]
    else:
        raise ValueError(
            f"unknown model preset {preset!r}; have "
            f"{sorted(PRESETS) + sorted(MM_PRESETS)}"
        )
    overrides = dict(model_spec.get("overrides", {}))
    if overrides:
        cfg = cfg.replace(**overrides)
    lora_spec = model_spec.get("lora")
    if lora_spec is not None:
        cfg = cfg.replace(lora=LoRAConfig(**lora_spec))
    return cfg


def build_train_config(spec: dict):
    from .trainer import TrainConfig

    fields = {f.name for f in dataclasses.fields(TrainConfig)}
    raw = dict(spec.get("training", {}))
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"unknown training fields: {sorted(unknown)}")
    return TrainConfig(**raw)


def build_mesh(spec: dict):
    from ..parallel.mesh import MeshSpec

    return MeshSpec(**spec.get("mesh", {})).build()


def build_batches(
    spec: dict, model_cfg, train_cfg, local_batch_size: int,
    shard_index: int, shard_count: int, split: str = "train",
):
    from ..data.loader import jsonl_token_batches
    from ..data.synthetic import synthetic_batches

    ds = spec.get("dataset", {})
    path = ds.get("eval_path") if split == "eval" else ds.get("path")
    if train_cfg.task in ("dpo", "rlhf", "reward"):
        # preference-pair streams (data/preference.py): chosen/rejected
        # token+mask leaves instead of the SFT tokens/loss_mask pair
        # (the reward task trains its Bradley–Terry head on this same path)
        from ..data.preference import (
            preference_jsonl_batches,
            synthetic_preference_batches,
        )

        if path:
            return preference_jsonl_batches(
                path,
                batch_size=local_batch_size,
                seq_len=train_cfg.seq_len,
                tokenizer_file=ds.get("tokenizer_file"),
                seed=train_cfg.seed,
                shard_index=shard_index,
                shard_count=shard_count,
            )
        if split == "eval" and ds.get("path"):
            # real preference data but no eval split configured: nothing held
            # out — run_job turns this into the explicit 'no eval split'
            # error rather than silently evaluating on synthetic pairs
            return None
        # eval holds out a disjoint seed region, like the SFT synthetic path
        seed = train_cfg.seed + shard_index + (
            100_003 if split == "eval" else 0
        )
        return synthetic_preference_batches(
            batch_size=local_batch_size,
            seq_len=train_cfg.seq_len,
            vocab_size=model_cfg.vocab_size,
            seed=seed,
        )
    if path and model_cfg.image_size:
        # image-bearing rows: one sample per row, pixels resized to the
        # model's vision tower (data/mm_loader.py)
        from ..data.mm_loader import mm_jsonl_batches

        return mm_jsonl_batches(
            path,
            batch_size=local_batch_size,
            seq_len=train_cfg.seq_len,
            image_size=model_cfg.image_size,
            tokenizer_file=ds.get("tokenizer_file"),
            seed=train_cfg.seed,
            shard_index=shard_index,
            shard_count=shard_count,
            normalize=ds.get("image_normalize", "clip"),
        )
    if path:
        return jsonl_token_batches(
            path,
            batch_size=local_batch_size,
            seq_len=train_cfg.seq_len,
            tokenizer_file=ds.get("tokenizer_file"),
            seed=train_cfg.seed,
            shard_index=shard_index,
            shard_count=shard_count,
        )
    if split == "eval" and ds.get("path"):
        # real train data but no eval split configured: nothing held out
        return None
    synth = ds.get("synthetic", {})
    # multimodal configs get pixels sized to their vision tower automatically
    image_size = model_cfg.image_size
    # the eval stream draws from a disjoint region of the generator's seed
    # space so held-out rows never coincide with training rows
    seed = train_cfg.seed + shard_index + (100_003 if split == "eval" else 0)
    return synthetic_batches(
        batch_size=local_batch_size,
        seq_len=train_cfg.seq_len,
        vocab_size=model_cfg.vocab_size,
        task=synth.get("task", "brightness" if image_size else "increment"),
        seed=seed,
        image_size=image_size,
    )


def run_job(spec: dict) -> None:
    from ..parallel.distributed import maybe_initialize_distributed, is_rank_zero
    from .trainer import Trainer

    # A job spec's ``build_trainer_spec`` stows user arguments it did not map
    # into trainer knobs under ``extra_arguments``. Silently ignoring them
    # would mean a user's hyperparameter never reaches the run — fail loudly
    # so plugin spec authors consume every argument they declare.
    extra = spec.get("extra_arguments")
    if extra:
        raise ValueError(
            f"unconsumed extra_arguments {sorted(extra)}: the job spec must map "
            "every user argument into the trainer spec (override "
            "build_trainer_spec in the spec class)"
        )

    artifacts_dir = spec["artifacts_dir"]
    os.makedirs(artifacts_dir, exist_ok=True)

    import jax

    from ..platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    maybe_initialize_distributed()

    model_cfg = build_model_config(spec)
    train_cfg = build_train_config(spec)
    mesh = build_mesh(spec)
    logger.info(
        "job %s: %s params=%.1fM mesh=%s devices=%d (%s) compile_cache=%s",
        spec.get("job_id", "?"), spec.get("model", {}).get("preset"),
        model_cfg.param_count() / 1e6, dict(zip(mesh.axis_names, mesh.devices.shape)),
        jax.device_count(), jax.devices()[0].device_kind, cache_dir,
    )
    if is_rank_zero():
        with open(os.path.join(artifacts_dir, "resolved_config.json"), "w") as f:
            json.dump(spec, f, indent=2, default=str)

    if train_cfg.task == "sft":
        trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    elif train_cfg.task in ("dpo", "rlhf"):
        from ..prefs.dpo_trainer import DPOTrainer

        # in-process rlhf forces prefetch=0 inside DPOTrainer (the actor
        # runs inline); rollout_workers > 0 keeps prefetch + async commits
        trainer = DPOTrainer(model_cfg, train_cfg, mesh=mesh)
    elif train_cfg.task == "reward":
        from ..prefs.reward_trainer import RewardModelTrainer

        trainer = RewardModelTrainer(model_cfg, train_cfg, mesh=mesh)
    else:
        raise ValueError(
            f"unknown training task {train_cfg.task!r}; one of "
            "['sft', 'dpo', 'rlhf', 'reward']"
        )
    plane = None
    if train_cfg.task == "rlhf":
        from ..prefs.learner import RolloutConfig, build_rlhf_loop

        rollout_spec = dict(spec.get("rollout", {}))
        if train_cfg.rollout_workers > 0:
            # disaggregated data plane: remote actor worker processes
            # stream pairs in over the rollout RPCs (prefs/rollout_plane.py)
            from ..prefs.rollout_plane import build_remote_rlhf_loop

            batches, plane, _buffer = build_remote_rlhf_loop(
                trainer, artifacts_dir,
                rollout=RolloutConfig(**rollout_spec),
                pretrained_dir=spec.get("model", {}).get("weights_dir"),
                model_spec=spec.get("model", {}),
            )
        else:
            batches, actor, _buffer = build_rlhf_loop(
                trainer, artifacts_dir,
                rollout=RolloutConfig(**rollout_spec),
                pretrained_dir=spec.get("model", {}).get("weights_dir"),
            )
    else:
        batches = build_batches(
            spec, model_cfg, train_cfg,
            local_batch_size=trainer.local_batch_size,
            shard_index=jax.process_index(), shard_count=jax.process_count(),
        )
    eval_batches = None
    if train_cfg.eval_every > 0:
        eval_batches = build_batches(
            spec, model_cfg, train_cfg,
            local_batch_size=trainer.local_batch_size,
            shard_index=jax.process_index(), shard_count=jax.process_count(),
            split="eval",
        )
        if eval_batches is None:
            raise ValueError(
                "training.eval_every > 0 but the dataset has no eval split: "
                "set dataset.eval_path (or use a synthetic dataset, which "
                "holds out a disjoint stream automatically)"
            )
    try:
        state = trainer.fit(
            batches, artifacts_dir,
            pretrained_dir=spec.get("model", {}).get("weights_dir"),
            eval_batches=eval_batches,
        )
        # deployable artifacts: PEFT adapter (+ merged checkpoint if
        # configured; the base dir enables the multi-host merge's host-side
        # reload)
        trainer.export_artifacts(
            state, artifacts_dir,
            pretrained_dir=spec.get("model", {}).get("weights_dir"),
        )
    finally:
        if plane is not None:
            # remote actor workers are child processes: reap them even when
            # fit raises, or a failed learner leaks a decoding fleet
            plane.close()

    if is_rank_zero():
        with open(os.path.join(artifacts_dir, "done.txt"), "w") as f:
            f.write("done\n")
    logger.info("job %s finished", spec.get("job_id", "?"))


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="ftc-train")
    parser.add_argument("--spec", required=True, help="path to the job-spec JSON")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        stream=sys.stdout,
        force=True,
    )
    with open(args.spec) as f:
        spec = json.load(f)
    run_job(spec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
