"""Resolve and load a promoted job's checkpoint into serving weights.

The deploy-bucket prefix a promotion copies (``controller/promotion.py``) is
the artifact layout the trainer produced: ``resolved_config.json`` (the job
spec — model preset + overrides + LoRA rank + training knobs),
``checkpoints/step_N/`` (trainable tree + opt state), plus adapter/merged
exports.  This module closes the loop the reference leaves open: it turns
that prefix back into ``(model, variables)`` the serving engine can decode
with.

Load path: rebuild the model from ``resolved_config.json`` exactly as the
trainer did (same preset, same seed ⇒ same frozen base for from-scratch test
jobs; same ``pretrained_weights_dir`` for real ones), restore the latest
checkpoint's trainable tree into it, then — for LoRA jobs — optionally fold
the adapter deltas into the base kernels so the serving matmul count drops to
the dense model's.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
from pathlib import Path
from typing import Any

from ..controller.objectstore import ObjectStore
from ..controller.schemas import JobRecord, PromotionStatus
from ..controller.statestore import StateStore

logger = logging.getLogger(__name__)


class ServeLoadError(RuntimeError):
    """A job cannot be served; ``status`` maps to the HTTP response."""

    def __init__(self, message: str, status: int = 409):
        super().__init__(message)
        self.status = status


async def resolve_promoted(state: StateStore, job_id: str) -> JobRecord:
    """The serve-side gate: only a COMPLETED promotion is servable.

    IN_PROGRESS/DELETING would read a half-copied prefix; FAILED and
    NOT_PROMOTED have no (trustworthy) deploy copy at all.  The error names
    the observed state so operators see *why*, not just a 409.
    """
    job = await state.get_job(job_id)
    if job is None:
        raise ServeLoadError(f"job {job_id!r} not found", status=404)
    if job.promotion_status is not PromotionStatus.COMPLETED:
        raise ServeLoadError(
            f"job {job_id!r} is not servable: promotion_status is "
            f"{job.promotion_status.value!r} (serving requires 'completed' — "
            "promote the job and wait for the copy to finish)"
        )
    if not job.promotion_uri:
        raise ServeLoadError(
            f"job {job_id!r} has promotion_status=completed but no "
            "promotion_uri recorded — re-promote it"
        )
    return job


async def fetch_promoted(
    store: ObjectStore, promotion_uri: str, dest_dir: Path | str
) -> Path:
    """Stage the servable slice of the deploy prefix to a local directory:
    the resolved job spec + the checkpoints tree (adapter/merged exports and
    metrics are not needed to serve)."""
    import shutil

    dest = Path(dest_dir)
    # stage FRESH: leftovers from a previous load (e.g. a higher step_N from
    # a promotion that was since rolled back and re-promoted) would win the
    # latest-checkpoint pick and silently serve stale weights
    if dest.exists():
        await asyncio.to_thread(shutil.rmtree, dest, ignore_errors=True)
    prefix = promotion_uri.rstrip("/") + "/"
    objs = await store.list_prefix(promotion_uri)
    if not objs:
        raise ServeLoadError(f"no objects under promotion uri {promotion_uri}")
    n = 0
    for obj in objs:
        rel = obj["uri"][len(prefix):]
        if rel != "resolved_config.json" and not rel.startswith("checkpoints/"):
            continue
        await store.get_file(obj["uri"], dest / rel)
        n += 1
    if n == 0:
        raise ServeLoadError(
            f"promotion prefix {promotion_uri} holds no resolved_config.json/"
            "checkpoints — was this job trained by this stack?"
        )
    logger.info("staged %d promoted objects <- %s", n, promotion_uri)
    return dest


def merge_lora_variables(model_cfg: Any, variables: dict) -> tuple[Any, dict]:
    """Fold LoRA deltas into the base kernels: ``W' = W + (α/r)·A·B``.

    Returns a rank-0 config and a variables tree without the ``lora``
    collection — the serving forward then runs the dense matmul count.  The
    merge happens in the param dtype (f32), matching ``hf_export``'s merged
    checkpoint math.  Quantized bases refuse (int4 kernels cannot absorb a
    dense delta); serve those unmerged.
    """
    import jax.numpy as jnp

    if "lora" not in variables:
        return model_cfg, variables
    if getattr(model_cfg, "quantize_base", False):
        raise ServeLoadError(
            "cannot merge LoRA into an int4-quantized base; serve unmerged"
        )
    scale = model_cfg.lora.alpha / model_cfg.lora.rank

    def merge(params: dict, lora: dict) -> dict:
        out = {}
        for key, sub in params.items():
            if key in lora and isinstance(lora[key], dict) \
                    and "lora_a" in lora[key]:
                a, b = lora[key]["lora_a"], lora[key]["lora_b"]
                kernel = sub["kernel"]
                # jnp.matmul batches over the leading layer axis of scanned
                # models ((L, in, r) @ (L, r, out)) and is a plain matmul on
                # unscanned ones
                delta = jnp.matmul(
                    a.astype(jnp.float32), b.astype(jnp.float32)
                ) * scale
                out[key] = {
                    **sub, "kernel": (
                        kernel.astype(jnp.float32) + delta
                    ).astype(kernel.dtype),
                }
            elif key in lora and isinstance(sub, dict):
                out[key] = merge(sub, lora[key])
            else:
                out[key] = sub
        return out

    merged = dict(variables)
    lora = merged.pop("lora")
    merged["params"] = merge(dict(merged["params"]), dict(lora))
    from ..models.lora import LoRAConfig

    merged_cfg = model_cfg.replace(
        lora=LoRAConfig(rank=0, alpha=model_cfg.lora.alpha,
                        targets=model_cfg.lora.targets)
    )
    return merged_cfg, merged


def _resolve_staged_spec(local_dir: Path) -> tuple[dict, Any, int]:
    """Shared validation for a staged promoted prefix: spec read,
    serving-eligibility guards, latest committed checkpoint step.  BOTH
    serve-side load paths run it — the in-process weight load below and the
    weights-free :func:`stage_meta` the cross-process transport uses — so a
    checkpoint is servable (or refused, with the same error) regardless of
    ``serve_transport``."""
    spec_path = local_dir / "resolved_config.json"
    if not spec_path.exists():
        raise ServeLoadError(
            f"{spec_path} missing: the promoted prefix carries no job spec"
        )
    with open(spec_path) as f:
        spec = json.load(f)

    from ..train.checkpoint import CheckpointManager
    from ..train.cli import build_model_config

    model_cfg = build_model_config(spec)
    if getattr(model_cfg, "vision", None) is not None:
        raise ServeLoadError("serving multimodal checkpoints is not supported yet")
    if getattr(model_cfg, "n_experts", 0):
        raise ServeLoadError(
            "serving MoE checkpoints is not supported (batching invariance "
            "does not hold under capacity routing)"
        )
    ckpt_dir = local_dir / "checkpoints"
    if not ckpt_dir.is_dir() or not os.listdir(ckpt_dir):
        raise ServeLoadError(
            f"no checkpoints under {ckpt_dir} — the job produced none"
        )
    latest = CheckpointManager(str(ckpt_dir)).latest_step()
    if latest is None:
        raise ServeLoadError(f"no committed checkpoint steps under {ckpt_dir}")
    return spec, model_cfg, latest


def load_serving_model(
    local_dir: Path | str, *, merge_lora: bool = True
) -> tuple[Any, dict, dict]:
    """Build ``(model, variables, meta)`` from a staged promoted prefix.

    Heavy (JAX init + checkpoint IO) and synchronous — callers run it in a
    thread (``asyncio.to_thread``) off the event loop.
    """
    local_dir = Path(local_dir)
    spec, model_cfg, latest = _resolve_staged_spec(local_dir)

    from ..train.checkpoint import CheckpointManager
    from ..train.cli import build_train_config
    from ..train.trainer import Trainer

    train_cfg = build_train_config(spec)
    if train_cfg.task == "reward":
        # a reward job's checkpoints carry {"lora", "head"} as the trainable
        # tree; the plain Trainer's restore template (lora only) would
        # refuse them — the reward trainer's template matches and its
        # _assemble drops the head (which serves via the reward_score RPC,
        # not through model.apply)
        from ..prefs.reward_trainer import RewardModelTrainer

        trainer = RewardModelTrainer(model_cfg, train_cfg)
    else:
        trainer = Trainer(model_cfg, train_cfg)
    state = trainer.init_state()
    ckpt = CheckpointManager(str(local_dir / "checkpoints"))
    template = trainer.state_to_host(state)
    host = ckpt.restore(latest, like=template)

    pretrained = spec.get("model", {}).get("weights_dir")
    if pretrained:
        state = trainer.load_pretrained(state, pretrained)
    variables = trainer._assemble(state.frozen, host["trainable"])

    # shard-audit trap (analysis/shard_audit.py, FTC_SHARD_AUDIT): the
    # assembled serving tree's device leaves must carry the rule table's
    # shardings — a restore path that landed the base replicated would make
    # every decode pay a silent GSPMD reshard (host-side numpy leaves carry
    # no sharding and are skipped)
    from ..analysis.shard_audit import ShardAuditor

    auditor = ShardAuditor.from_env(name="serve-load")
    if auditor is not None:
        from ..parallel.sharding import sharding_for_tree

        expected = sharding_for_tree(variables, trainer.mesh, trainer.rules)
        auditor.audit(variables, expected, label=f"serve-load:step_{latest}")

    model = trainer.model
    merged = False
    if merge_lora and "lora" in variables \
            and not getattr(model_cfg, "quantize_base", False):
        model_cfg, variables = merge_lora_variables(model_cfg, variables)
        model = type(model)(cfg=model_cfg)
        merged = True

    meta = {
        "preset": spec.get("model", {}).get("preset"),
        "task": train_cfg.task,
        "checkpoint_step": latest,
        "lora_merged": merged,
        "vocab_size": model_cfg.vocab_size,
        "max_seq_len": model_cfg.max_seq_len,
        "weights_dir": pretrained or None,
    }
    logger.info("serving model ready: %s", meta)
    # a process that serves never steps: its start-up ends with its load
    from ..obs import trace

    trace.STARTUP.close()
    return model, variables, meta


def strip_lora_for_multitenant(
    model: Any, variables: dict
) -> tuple[Any, dict, Any | None, float, int]:
    """Split a loaded (unmerged) serving model into the pristine base plus
    its own adapter, for multi-tenant serving (docs/serving.md §Multi-tenant
    adapters): returns ``(base_model, base_variables, lora_tree | None,
    alpha, rank)``.  The base model's config drops to rank 0 — per-lane
    adapters apply through the ``"tenants"`` stacks instead, so the job's
    own fine-tune becomes tenant #1 and slot 0 stays the untouched base."""
    if "lora" not in variables:
        return model, variables, None, 0.0, 0
    variables = dict(variables)
    lora_tree = variables.pop("lora")
    cfg = model.cfg
    alpha, rank = cfg.lora.alpha, cfg.lora.rank
    from ..models.lora import LoRAConfig

    base_cfg = cfg.replace(
        lora=LoRAConfig(rank=0, alpha=alpha, targets=cfg.lora.targets)
    )
    return type(model)(cfg=base_cfg), variables, lora_tree, alpha, rank


def _load_adapter_tree(local_dir: Path | str) -> tuple[Any, dict]:
    """Worker-thread body of :func:`load_adapter`: the staged prefix →
    ``(lora_tree, adapter_meta)``.  Unlike :func:`load_serving_model` this
    never builds the model or touches base weights — the checkpoint's
    trainable tree IS the adapter for a LoRA job (``Trainer._assemble``), so
    the whole load is one spec read plus one (small) msgpack restore."""
    local_dir = Path(local_dir)
    spec_path = local_dir / "resolved_config.json"
    if not spec_path.exists():
        raise ServeLoadError(
            f"{spec_path} missing: the promoted prefix carries no job spec"
        )
    with open(spec_path) as f:
        spec = json.load(f)

    from ..train.checkpoint import CheckpointManager
    from ..train.cli import build_model_config

    model_cfg = build_model_config(spec)
    if getattr(model_cfg, "vision", None) is not None:
        raise ServeLoadError("multimodal adapters are not servable yet")
    if model_cfg.lora.rank < 1:
        raise ServeLoadError(
            "job is not a LoRA job (lora.rank == 0): only LoRA deltas can "
            "be multiplexed onto a shared base fleet — serve it as its own "
            "model instead"
        )
    ckpt_dir = local_dir / "checkpoints"
    if not ckpt_dir.is_dir() or not os.listdir(ckpt_dir):
        raise ServeLoadError(
            f"no checkpoints under {ckpt_dir} — the job produced none"
        )
    ckpt = CheckpointManager(str(ckpt_dir))
    latest = ckpt.latest_step()
    if latest is None:
        raise ServeLoadError(f"no committed checkpoint steps under {ckpt_dir}")
    host = ckpt.restore(latest)  # raw state dict: no template needed
    lora_tree = host.get("trainable") if isinstance(host, dict) else None
    if not isinstance(lora_tree, dict) or not lora_tree:
        raise ServeLoadError(
            "checkpoint carries no trainable (LoRA) tree — was this job "
            "trained by this stack in LoRA mode?"
        )
    meta = {
        "preset": spec.get("model", {}).get("preset"),
        "weights_dir": spec.get("model", {}).get("weights_dir") or None,
        "checkpoint_step": latest,
        "lora_rank": model_cfg.lora.rank,
        "lora_alpha": model_cfg.lora.alpha,
    }
    return lora_tree, meta


async def load_adapter(
    state: StateStore,
    store: ObjectStore,
    job_id: str,
    work_dir: Path | str,
    *,
    base_meta: dict | None = None,
) -> tuple[Any, dict]:
    """Stage ONLY a promoted LoRA job's adapter deltas for multi-tenant
    serving (docs/serving.md §Multi-tenant adapters).

    The base fleet already holds the model weights; this path resolves the
    tenant job's promotion, stages its spec + checkpoints (the trainable
    tree of a LoRA job is just the adapter — megabytes, not the gigabytes a
    full model load moves), and returns ``(lora_tree, meta)`` ready for
    :meth:`~finetune_controller_tpu.serve.adapters.AdapterRegistry.register`.

    ``base_meta`` (the serving session's model meta) guards against serving
    an adapter on the wrong base: preset and pretrained weights must match —
    KV and deltas computed against different bases are silently wrong, the
    worst failure mode a 409 can prevent.
    """
    import shutil
    import uuid

    job = await resolve_promoted(state, job_id)
    job_dir = Path(work_dir) / job_id
    local = await fetch_promoted(
        store, job.promotion_uri, job_dir / f"adapter-{uuid.uuid4().hex[:8]}"
    )
    try:
        lora_tree, meta = await asyncio.to_thread(_load_adapter_tree, local)
    finally:
        await asyncio.to_thread(shutil.rmtree, local, ignore_errors=True)
    if base_meta is not None:
        for field in ("preset", "weights_dir"):
            if meta.get(field) != base_meta.get(field):
                raise ServeLoadError(
                    f"adapter job {job_id!r} was trained on "
                    f"{field}={meta.get(field)!r} but the base fleet serves "
                    f"{field}={base_meta.get(field)!r} — an adapter only "
                    "composes with the exact base it was trained against"
                )
        if base_meta.get("lora_merged"):
            raise ServeLoadError(
                "the base fleet serves MERGED weights; multi-tenant "
                "adapters need the pristine base — reload it with "
                "serve_merge_lora=false"
            )
    meta["job_id"] = job_id
    meta["promotion_uri"] = job.promotion_uri
    return lora_tree, meta


def stage_meta(local_dir: Path | str, *, merge_lora: bool = True) -> dict:
    """Serving meta from a STAGED promoted prefix without building the model
    or touching weights — the process-transport path (docs/serving.md
    §Cross-process transport): the control plane stages the prefix once and
    the worker processes rebuild the weights themselves
    (``transport/builders.py::deploy_dir``), so the API process only ever
    reads the spec + the checkpoint directory listing.  Eligibility guards
    are :func:`_resolve_staged_spec`, shared with the in-process load path
    — both transports accept and refuse exactly the same checkpoints."""
    local_dir = Path(local_dir)
    spec, model_cfg, latest = _resolve_staged_spec(local_dir)
    # predicts what load_serving_model's merge does in the worker: a LoRA
    # checkpoint ("lora" in the assembled variables ⇔ rank > 0) folds into
    # the base unless quantized
    merged = bool(
        merge_lora and model_cfg.lora.rank > 0
        and not getattr(model_cfg, "quantize_base", False)
    )
    pretrained = spec.get("model", {}).get("weights_dir")
    return {
        "preset": spec.get("model", {}).get("preset"),
        "task": spec.get("training", {}).get("task", "sft"),
        "checkpoint_step": latest,
        "lora_merged": merged,
        "lora_rank": model_cfg.lora.rank,
        "lora_alpha": model_cfg.lora.alpha,
        "vocab_size": model_cfg.vocab_size,
        "max_seq_len": model_cfg.max_seq_len,
        "weights_dir": pretrained or None,
    }


async def stage_for_workers(
    state: StateStore,
    store: ObjectStore,
    job_id: str,
    work_dir: Path | str,
    *,
    merge_lora: bool = True,
) -> tuple[Path, dict]:
    """resolve → stage → meta, WITHOUT loading weights into this process —
    the serve-side path when replicas are worker processes.  The staged dir
    is returned (NOT removed: workers read it for as long as the generation
    serves) along with the same meta shape :func:`load_promoted` produces."""
    import uuid

    job = await resolve_promoted(state, job_id)
    job_dir = Path(work_dir) / job_id
    local = await fetch_promoted(
        store, job.promotion_uri, job_dir / f"workers-{uuid.uuid4().hex[:8]}"
    )
    meta = await asyncio.to_thread(stage_meta, local, merge_lora=merge_lora)
    meta["job_id"] = job_id
    meta["promotion_uri"] = job.promotion_uri
    return local, meta


async def load_promoted(
    state: StateStore,
    store: ObjectStore,
    job_id: str,
    work_dir: Path | str,
    *,
    merge_lora: bool = True,
) -> tuple[Any, dict, dict]:
    """resolve → stage → load, the whole serve-side path for one job.

    Each load stages into its OWN ``stage-<nonce>`` directory and removes it
    once the weights are in memory: two racing loads for the same job (or a
    load racing a rollover) can no longer interleave writes under one shared
    prefix — the last-writer-wins corruption ISSUE 10 names.  Winner
    selection between racing callers happens one level up
    (``ServeManager.load``'s per-job single-flight CAS); this layer just
    guarantees that even uncoordinated concurrent loads are each internally
    consistent.  (A crashed load can leak its stage dir; no sweep happens
    here on purpose — a sweep would race a concurrent load's live staging,
    which is the exact bug being fixed.)
    """
    import shutil
    import uuid

    job = await resolve_promoted(state, job_id)
    job_dir = Path(work_dir) / job_id
    local = await fetch_promoted(
        store, job.promotion_uri, job_dir / f"stage-{uuid.uuid4().hex[:8]}"
    )
    try:
        model, variables, meta = await asyncio.to_thread(
            load_serving_model, local, merge_lora=merge_lora
        )
    finally:
        await asyncio.to_thread(shutil.rmtree, local, ignore_errors=True)
    meta["job_id"] = job_id
    meta["promotion_uri"] = job.promotion_uri
    return model, variables, meta
