"""The trainer: sharded init, jitted train step, fit loop with metrics,
checkpointing and preemption handling.

Everything device-side happens inside two jitted functions (``_init_fn`` and
``_step_fn``) whose in/out shardings come from ``parallel.sharding`` rules, so
the same code runs single-chip, on a CPU test mesh, or across a v5e slice —
only the MeshSpec changes.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import sys
import time
from functools import partial
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import platform
from ..models.llama import SOWN, LlamaConfig, LlamaForCausalLM
from ..obs import trace as obs_trace
from ..obs.trace import annotate
from ..ops.attention import resolve_attention_impl
from ..parallel.mesh import MeshSpec
from ..parallel.sharding import (
    LLAMA_RULES,
    PartitionRules,
    batch_sharding,
    sharding_for_tree,
)
from .checkpoint import CheckpointManager, reshard
from .losses import next_token_loss
from .metrics import MetricsWriter
from .optimizer import build_optimizer

logger = logging.getLogger(__name__)


@struct.dataclass
class TrainState:
    step: jax.Array
    frozen: Any        # non-trainable variables ({} in full fine-tune mode)
    trainable: Any     # differentiated + optimized tree
    opt_state: Any


@dataclasses.dataclass
class TrainConfig:
    mode: str = "lora"            # "lora" | "full"
    #: training objective: "sft" (next-token cross-entropy, this class) |
    #: "dpo" (preference pairs through ``prefs.dpo_trainer.DPOTrainer``) |
    #: "rlhf" (actor/learner loop, ``prefs/learner.py`` — DPO over on-policy
    #: rollouts).  ``train/cli.py`` selects the trainer class from this.
    task: str = "sft"
    #: DPO inverse-temperature (KL strength) — used by the dpo/rlhf tasks only
    dpo_beta: float = 0.1
    #: rlhf only: number of REMOTE rollout actor processes (0 = the
    #: in-process actor/learner gang).  > 0 selects the disaggregated data
    #: plane (``prefs/rollout_plane.py``): actors run as serve-fleet tenants
    #: in their own worker processes, stream scored pairs over the rollout
    #: RPCs, and receive policy rollovers as pushed adapter deltas — so the
    #: learner keeps async checkpoint commits and prefetch
    #: (docs/preference.md §Disaggregated rollouts).
    rollout_workers: int = 0
    learning_rate: float = 2e-4
    warmup_steps: int = 10
    total_steps: int = 100
    schedule: str = "cosine"
    weight_decay: float = 0.0
    clip_norm: float = 1.0
    batch_size: int = 8           # global
    seq_len: int = 512
    seed: int = 0
    log_every: int = 10
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    #: capture a jax.profiler trace for N steps (0 = off); the trace lands in
    #: {artifacts_dir}/profile and ships with the artifacts (SURVEY.md §5.1 —
    #: the reference has no tracing at all)
    profile_steps: int = 0
    #: first profiled step (default skips the compile step)
    profile_start_step: int = 2
    #: GPipe microbatches when the mesh has pp > 1 (0 = 2·pp, a reasonable
    #: bubble/memory tradeoff); must divide the per-dp-shard batch
    pp_microbatches: int = 0
    #: also write a merged full HF checkpoint at the end of a LoRA run
    #: (adapter-only PEFT export always happens for text LoRA runs)
    export_merged: bool = False
    #: storage dtype for the FROZEN base params in lora mode (e.g. "bfloat16"
    #: halves their HBM footprint and per-step weight traffic; the compute
    #: path already runs bf16 so only the storage rounding changes). None
    #: keeps the model's param_dtype. Int4 kernels and their bf16 scales
    #: (models/quant.py) pass through untouched.
    frozen_dtype: str | None = None
    #: run a held-out evaluation every N steps (0 = off); requires an eval
    #: batch stream passed to ``fit(eval_batches=...)``
    eval_every: int = 0
    #: batches averaged per evaluation pass
    eval_steps: int = 8
    #: split each optimizer step's global batch into N sequential
    #: microbatches (``lax.scan`` inside the jitted step), accumulating
    #: gradients — the standard dial for batch sizes whose activations don't
    #: fit HBM. batch_size must divide by it; numerics match the unsplit
    #: step up to float reduction order (tested).
    grad_accum_steps: int = 1
    #: host input-pipeline prefetch depth (``data/prefetch.py``): a
    #: background thread builds up to N batches ahead while the device runs
    #: the current step, preserving batch order exactly (loss trajectories
    #: are bit-identical to the synchronous path — tested). 0 is the escape
    #: hatch back to the synchronous on-thread build.
    prefetch: int = 2
    #: also ``device_put`` the NEXT batch with the training-step sharding on
    #: the prefetch thread (double-buffered host→HBM copy that overlaps the
    #: running step). Ignored when ``prefetch == 0``.
    prefetch_to_device: bool = True
    #: recompilation guard (``analysis/recompile_guard.py``): budget of
    #: distinct jit signatures the step/eval functions may compile over the
    #: whole run (0 = off). A healthy run compiles once per batch structure;
    #: a per-step-varying shape (or static Python value) blows straight
    #: past this.
    recompile_budget: int = 0
    #: what to do past the budget: "warn" (log once) or "raise"
    recompile_action: str = "warn"
    #: transfer guard (``analysis/transfer_guard.py``): wrap the jitted
    #: step's dispatch window so any device<->host transfer inside it —
    #: an implicit host->device copy of a stray numpy leaf, a leftover
    #: ``jax.device_get`` — fails loudly instead of silently serializing
    #: every step.  "raise" | "warn" | "off"; the empty default inherits
    #: ``FTC_TRANSFER_GUARD`` from the env (off when unset).  The benchmark
    #: (``benchmarks/harness/drivers/train.py``) arms "raise".
    transfer_guard: str = ""
    #: shard audit (``analysis/shard_audit.py``): at checkpoint/restore
    #: boundaries, assert every live state leaf's ``.sharding`` still equals
    #: the rule table's expected ``NamedSharding`` — catching the silent
    #: full replication an elastic restore or resharding path can introduce
    #: (every later step then pays a GSPMD reshard that profiles as "slow",
    #: never as an error).  "raise" | "warn" | "off"; the empty default
    #: inherits ``FTC_SHARD_AUDIT`` from the env (off when unset).
    #: The benchmark arms "raise" so a mis-sharded timed run aborts.
    shard_audit: str = ""
    #: liveness heartbeat cadence (``resilience/heartbeat.py``): rank 0
    #: writes ``heartbeat.json`` (step + wall clock) into the artifacts dir
    #: at most every N seconds; the artifact sync ships it and the monitor's
    #: lease check uses it to catch silently-stuck jobs. 0 disables.
    heartbeat_interval_s: float = 10.0
    #: observability (docs/observability.md): rank 0 records lifecycle
    #: events (``events.jsonl``), spans (``trace/trainer.jsonl``), and the
    #: step-phase split (``phase_*_ms`` CSV columns).  ``FTC_TRACE=0`` in the
    #: env is the operator kill switch.
    trace: bool = True


class PreemptionGuard:
    """SIGTERM → save-and-exit flag (TPU spot/maintenance preemption)."""

    def __init__(self):
        self.requested = False

    def install(self) -> None:
        def handler(signum, frame):
            logger.warning("preemption signal %s received; will checkpoint and exit", signum)
            self.requested = True

        signal.signal(signal.SIGTERM, handler)


def _adapt_loaded_params(loaded: Any, target: Any, *, quant_block: int) -> Any:
    """Recursively fit a converted HF tree onto the initialised param tree:
    shape/dtype-check every leaf and quantize kernels where the target stores
    int4 (QLoRA base weights). Leaves stay HOST-side numpy throughout — the
    caller reshards onto the mesh, so the unsharded model never has to fit a
    single device."""
    if not isinstance(target, dict):
        arr = np.asarray(loaded)
        if tuple(arr.shape) != tuple(target.shape):
            raise ValueError(
                f"pretrained tensor shape {tuple(arr.shape)} != model "
                f"{tuple(target.shape)} — config/checkpoint mismatch"
            )
        return arr.astype(target.dtype)
    out: dict[str, Any] = {}
    loaded = dict(loaded)
    # quantize every kernel the target stores int4: dense projections are
    # "kernel" -> "kernel_packed"/"kernel_scales", and so are the stacked MoE
    # experts' (models/moe.py: experts/<projection>/kernel). Leading axes
    # (scan layers, the expert axis) are vmapped generically.
    for pk in [k for k in target if k.endswith("_packed")]:
        stem = pk[: -len("_packed")]
        if stem not in loaded:
            continue  # surfaces as a missing-key error below
        from ..models.quant import quantize_int4

        kernel = np.asarray(loaded.pop(stem), np.float32)
        packed_t = target[pk]
        want = tuple(packed_t.shape[:-2]) + (
            packed_t.shape[-2] * 2, packed_t.shape[-1],
        )
        if tuple(kernel.shape) != want:
            raise ValueError(
                f"pretrained tensor {stem!r} shape {tuple(kernel.shape)} != "
                f"model {want} (pre-quantization) — config/checkpoint mismatch"
            )
        quant = partial(quantize_int4, block_size=quant_block)
        # quantize on the CPU backend when available so a model bigger than
        # one accelerator's HBM can still be converted (a tpu-only
        # jax_platforms pin has no cpu backend — use the default device then)
        try:
            ctx = jax.default_device(jax.devices("cpu")[0])
        except RuntimeError:
            import contextlib

            ctx = contextlib.nullcontext()
        with ctx:
            lead = kernel.shape[:-2]
            flat = kernel.reshape((-1,) + kernel.shape[-2:])
            packed, scales = jax.vmap(quant)(flat)
        out[pk] = np.asarray(packed).reshape(lead + packed.shape[1:])
        out[f"{stem}_scales"] = np.asarray(scales).reshape(
            lead + scales.shape[1:]
        )
    for key, tv in target.items():
        if key in out:
            continue
        if key not in loaded:
            raise ValueError(f"pretrained checkpoint missing {key!r}")
        out[key] = _adapt_loaded_params(loaded[key], tv, quant_block=quant_block)
    return out


class Trainer:
    def __init__(
        self,
        model_cfg: LlamaConfig,
        train_cfg: TrainConfig,
        mesh: Mesh | None = None,
        rules: PartitionRules = LLAMA_RULES,
    ):
        # on the process's start-up log (obs/trace.py::StartupLog) while that
        # is open; later to the fit's span recorder, or nowhere
        with obs_trace.STARTUP.span(
                "trainer.build", mode=train_cfg.mode) as span:
            self._construct(model_cfg, train_cfg, mesh, rules)
            cfg = getattr(self.model_cfg, "text", self.model_cfg)
            span["attributes"].update(
                n_layers=cfg.n_layers, scan_layers=cfg.scan_layers,
                mesh={k: v for k, v in self.mesh.shape.items() if v > 1})

    def _construct(self, model_cfg, train_cfg, mesh, rules) -> None:
        log = obs_trace.STARTUP
        self.cfg = train_cfg
        self.mesh = mesh if mesh is not None else MeshSpec(fsdp=1).build(
            platform.devices()[:1])
        #: what the step's attention runs as, for the ``train-started`` event
        self.attention_impl = resolve_attention_impl(
            model_cfg.attention_impl, train_cfg.seq_len, mesh=self.mesh)
        if self.attention_impl == "ring" and model_cfg.attention_impl != "ring":
            # the sp axis shards the sequence: the model carries the choice,
            # so a trace outside the step's installed mesh makes it too
            logger.info("sp=%d mesh axis active: attention_impl -> ring",
                        self.mesh.shape["sp"])
            model_cfg = model_cfg.replace(attention_impl="ring")
        if train_cfg.seq_len > getattr(model_cfg, "max_seq_len", train_cfg.seq_len):
            # RoPE extrapolates silently but badly past the trained range,
            # and HF exports carry max_position_embeddings = max_seq_len —
            # downstream inference would truncate what was trained here
            logger.warning(
                "seq_len %d exceeds the model's max_seq_len %d: RoPE "
                "positions run beyond the preset's trained range and the "
                "exported max_position_embeddings stays %d — use a "
                "long-context preset (e.g. mistral-7b-32k)",
                train_cfg.seq_len, model_cfg.max_seq_len, model_cfg.max_seq_len,
            )
        self.model_cfg = model_cfg
        self.rules = rules
        # Model family is selected by config type (the duck-type surface the
        # multimodal config mirrors) — BASELINE #5 trains through the same
        # trainer as the text families.
        from ..models.multimodal import LlavaConfig, LlavaForCausalLM

        self._is_multimodal = isinstance(model_cfg, LlavaConfig)
        if self._is_multimodal:
            self.model = LlavaForCausalLM(model_cfg)
        else:
            self.model = LlamaForCausalLM(model_cfg)

        self._pp = self.mesh.shape.get("pp", 1)
        model_cfg.refuse_mesh(dict(self.mesh.shape))
        if self._pp > 1:
            from ..parallel.pipeline import validate_pp_mesh

            validate_pp_mesh(self.mesh)
            if not model_cfg.scan_layers:
                raise ValueError("pp > 1 requires scan_layers=True (stacked params)")
            if model_cfg.n_layers % self._pp:
                raise ValueError(
                    f"n_layers {model_cfg.n_layers} not divisible by pp {self._pp}"
                )
            if model_cfg.lora.rank > 0 and model_cfg.lora.dropout > 0:
                raise ValueError("pp > 1 does not support LoRA dropout yet")
        if train_cfg.grad_accum_steps > 1:
            if train_cfg.batch_size % train_cfg.grad_accum_steps:
                raise ValueError(
                    f"batch_size {train_cfg.batch_size} not divisible by "
                    f"grad_accum_steps {train_cfg.grad_accum_steps}"
                )
            batch_shards = self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
            micro = train_cfg.batch_size // train_cfg.grad_accum_steps
            if micro % batch_shards:
                raise ValueError(
                    f"microbatch size {micro} (batch_size/grad_accum_steps) "
                    f"not divisible over the {batch_shards}-way batch sharding"
                )
        with log.span("trainer.build.optimizer"):
            self.tx, self.sched = build_optimizer(
                learning_rate=train_cfg.learning_rate,
                warmup_steps=train_cfg.warmup_steps,
                total_steps=train_cfg.total_steps,
                schedule=train_cfg.schedule,
                weight_decay=train_cfg.weight_decay,
                clip_norm=train_cfg.clip_norm,
            )
        self._state_shardings = None
        self._init_jit = None
        #: calls of step() so far: the profiler's step number
        self._steps_enqueued = 0
        self._warned_eval_unsplit = False
        #: commit EVERY checkpoint synchronously (not just the final one).
        #: Async saves are the throughput default; the rlhf learner flips
        #: this so the actor's next rollout round deterministically sees the
        #: just-committed step (prefs/learner.py)
        self._blocking_checkpoints = False
        #: stamped into every checkpoint manifest; elastic restore refuses a
        #: checkpoint written under a different rule table (train/elastic.py)
        self._rule_fingerprint = rules.fingerprint()
        self._build()

    # ---- construction ----------------------------------------------------

    # params trained alongside LoRA adapters on multimodal models: the LLaVA
    # recipe always trains the vision→text projector, adapters or not
    _MM_TRAINED_PARAMS = ("projector_fc1", "projector_fc2")

    def _split(self, variables: FrozenDict) -> tuple[Any, Any]:
        """(frozen, trainable) per the training mode."""
        variables = dict(variables)
        # drop what init sowed: re-feeding a collection to apply would make
        # flax append to the stale tuple and double-count it
        for collection in SOWN:
            variables.pop(collection, None)
        if self.cfg.mode == "lora":
            if "lora" not in variables:
                raise ValueError("mode='lora' but the model has no LoRA params; set lora.rank > 0")
            lora = variables.pop("lora")
            if not self._is_multimodal:
                return variables, lora
            params = dict(variables["params"])
            projector = {
                k: params.pop(k) for k in self._MM_TRAINED_PARAMS if k in params
            }
            variables["params"] = params
            return variables, {"lora": lora, "projector": projector}
        if self.cfg.mode == "full":
            trainable = variables.pop("params")
            return variables, trainable
        raise ValueError(f"unknown training mode {self.cfg.mode!r}")

    def _assemble(self, frozen: Any, trainable: Any) -> dict:
        out = dict(frozen)
        if self.cfg.mode != "lora":
            out["params"] = trainable
            return out
        if self._is_multimodal:
            out["lora"] = trainable["lora"]
            out["params"] = {**dict(out["params"]), **trainable["projector"]}
        else:
            out["lora"] = trainable
        return out

    def raw_init(self, rng: jax.Array) -> TrainState:
        """The unjitted, unsharded state constructor — what ``init_state``
        jits; ``jax.eval_shape`` of it gives the state's shapes."""
        import math

        # dummy init batch must be divisible over the batch and sp axes (ring
        # attention shards the sequence even at init trace time)
        b0 = self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
        s0 = math.lcm(8, self.mesh.shape.get("sp", 1))
        tokens = jnp.zeros((b0, s0), jnp.int32)
        if self._is_multimodal:
            size = self.model_cfg.vision.image_size
            pixels = jnp.zeros((b0, size, size, 3), jnp.float32)
            variables = self.model.init({"params": rng}, tokens, pixels)
        else:
            variables = self.model.init({"params": rng}, tokens)
        frozen, trainable = self._split(variables)
        frozen = self._cast_frozen(frozen)
        opt_state = self.tx.init(trainable)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            frozen=frozen,
            trainable=trainable,
            opt_state=opt_state,
        )

    _raw_init = raw_init

    def _cast_frozen(self, frozen: Any) -> Any:
        """Downcast float32 leaves of the frozen base to ``cfg.frozen_dtype``
        (lora mode only — full fine-tune keeps f32 master weights). Int4
        packed kernels pass through untouched (non-f32 dtypes), and so does
        every leaf the model says keeps its dtype (``keeps_dtype``)."""
        if not self.cfg.frozen_dtype or self.cfg.mode != "lora":
            return frozen
        dt = jnp.dtype(self.cfg.frozen_dtype)

        def cast(path, x):
            if x.dtype != jnp.float32 or self.model_cfg.keeps_dtype(path):
                return x
            return x.astype(dt)

        return jax.tree_util.tree_map_with_path(cast, frozen)

    def _build(self) -> None:
        log = obs_trace.STARTUP
        with log.span("trainer.build.rng"):
            # the first operation this process runs on the device
            rng = jax.random.PRNGKey(self.cfg.seed)
        with log.span("trainer.build.abstract_state", fun_name="raw_init"):
            shapes = self._state_shapes = jax.eval_shape(self.raw_init, rng)
        with log.span("trainer.build.shardings"):
            self._state_shardings = sharding_for_tree(
                shapes, self.mesh, self.rules)
            self._batch_sharding = batch_sharding(self.mesh)
            from ..parallel.mesh import AxisNames as Ax

            self._pixel_sharding = NamedSharding(self.mesh, P(Ax.BATCH_AXES))
        self._init_jit = jax.jit(self.raw_init, out_shardings=self._state_shardings)
        # jitted steps are cached per batch structure (multimodal batches add
        # a rank-4 pixels leaf whose sharding differs from token arrays)
        self._step_jits: dict[tuple[str, ...], Any] = {}
        with log.span("trainer.build.guards"):
            self._build_guards()

    def _build_guards(self) -> None:
        self._recompile_guard = None
        if self.cfg.recompile_budget > 0:
            from ..analysis.recompile_guard import RecompileGuard

            self._recompile_guard = RecompileGuard(
                self.cfg.recompile_budget,
                on_excess=self.cfg.recompile_action,
                name="trainer-recompile-guard",
            )
        self._transfer_guard = None
        mode = (self.cfg.transfer_guard or "").strip().lower()
        if mode in ("raise", "warn"):
            from ..analysis.transfer_guard import TransferGuard

            self._transfer_guard = TransferGuard(
                mode, name="trainer-transfer-guard"
            )
        elif mode == "":
            from ..analysis.transfer_guard import TransferGuard

            self._transfer_guard = TransferGuard.from_env(
                name="trainer-transfer-guard"
            )
        self._shard_auditor = None
        audit_mode = (self.cfg.shard_audit or "").strip().lower()
        if audit_mode in ("raise", "warn"):
            from ..analysis.shard_audit import ShardAuditor

            self._shard_auditor = ShardAuditor(
                audit_mode, name="trainer-shard-audit"
            )
        elif audit_mode == "":
            from ..analysis.shard_audit import ShardAuditor

            self._shard_auditor = ShardAuditor.from_env(
                name="trainer-shard-audit"
            )

    @property
    def state_shardings(self) -> Any:
        """The ``NamedSharding`` tree every ``TrainState`` of this trainer
        carries (a ``TrainState`` of shardings)."""
        return self._state_shardings

    @property
    def step_programs(self) -> dict[tuple[str, ...], Any]:
        """The jitted step functions built so far, keyed by the batch's
        sorted keys (``lower(...).compile()`` on one gives its
        ``memory_analysis()``)."""
        return self._step_jits

    def _audit_state_sharding(self, state: Any, label: str) -> None:
        """Shard-audit trap (analysis/shard_audit.py): at the
        checkpoint/restore boundaries, every live state leaf must still
        carry the rule table's NamedSharding — the bug class this catches
        is silent replication after an elastic restore."""
        if self._shard_auditor is not None:
            self._shard_auditor.audit(
                state, self._state_shardings, label=label
            )

    def _batch_leaf_sharding(self, x: Any) -> NamedSharding:
        """Token-like (B, S) leaves shard batch+seq; higher-rank leaves (e.g.
        pixels (B, H, W, 3)) shard the batch dim only — the sequence axis of an
        image is not the token sequence the sp ring shards."""
        if getattr(x, "ndim", 2) == 2:
            return self._batch_sharding
        return self._pixel_sharding

    def _get_step_jit(self, batch: dict):
        key = tuple(sorted(batch))
        fn = self._step_jits.get(key)
        if fn is None:
            batch_sh = {k: self._batch_leaf_sharding(batch[k]) for k in batch}
            # Donating the state reuses its buffers for the output — the HBM
            # lever that lets big states fit on TPU.  On the CPU test backend
            # it buys nothing (host RAM, no HBM pressure) and, combined with
            # the persistent compilation cache, deserialized executables have
            # been observed mis-aliasing donated scalars under a long test
            # session (a resumed step counter reading back as garbage), so
            # CPU skips donation — numerics are identical either way.
            donate = (0,) if jax.default_backend() != "cpu" else ()
            fn = jax.jit(
                self._train_step,
                in_shardings=(self._state_shardings, batch_sh),
                out_shardings=(self._state_shardings, None),
                donate_argnums=donate,
            )
            obs_trace.STARTUP.step_program(self._train_step.__name__)
            if self._recompile_guard is not None:
                fn = self._recompile_guard.wrap(fn, label=f"step:{','.join(key)}")
            if self._transfer_guard is not None:
                # the guarded window is the DISPATCH only: shard_batch has
                # already device_put the batch (explicitly — allowed), so a
                # steady-state step moves nothing across the boundary
                fn = self._transfer_guard.wrap(fn, label=f"step:{','.join(key)}")
            self._step_jits[key] = fn
        return fn

    # ---- device-side fns -------------------------------------------------

    @property
    def _use_dropout(self) -> bool:
        lora = self.model_cfg.lora
        return lora.rank > 0 and lora.dropout > 0.0

    def _loss_fn(self, trainable, frozen, batch, dropout_rng):
        variables = self._assemble(frozen, trainable)
        if self._pp > 1:
            # dropout_rng is intentionally unused here: the constructor
            # rejects pp>1 with LoRA dropout; if that guard is ever relaxed,
            # this branch must thread rngs through the pipeline too.
            assert not self._use_dropout, "pp path has no dropout support"
            from ..models.llama import pipelined_causal_lm_logits

            from ..parallel.pipeline import (
                bubble_fraction,
                default_pp_microbatches,
            )

            n_micro = self.cfg.pp_microbatches
            if not n_micro:
                local = batch["tokens"].shape[0] // (
                    self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
                )
                n_micro = default_pp_microbatches(local, self._pp)

            # trace-time (runs once per compilation, not per step)
            logger.info(
                "GPipe schedule: %d microbatches over %d stages — bubble "
                "fraction %.1f%%", n_micro, self._pp,
                100 * bubble_fraction(n_micro, self._pp),
            )
            logits = pipelined_causal_lm_logits(
                self.model_cfg, variables, batch["tokens"],
                mesh=self.mesh, n_micro=n_micro,
                segment_ids=batch.get("segment_ids"),
            )
            return next_token_loss(logits, batch["tokens"], batch.get("loss_mask"))
        rngs = {"dropout": dropout_rng} if self._use_dropout else None
        apply_kw: dict[str, Any] = dict(
            segment_ids=batch.get("segment_ids"),
            deterministic=not self._use_dropout,
            rngs=rngs,
        )
        if self._is_multimodal:
            apply_kw["pixels"] = batch.get("pixels")
        sown = self.model_cfg.sown
        if sown:
            logits, collections = self.model.apply(
                variables, batch["tokens"], mutable=sown, **apply_kw)
        else:
            logits = self.model.apply(variables, batch["tokens"], **apply_kw)
            collections = {}
        loss, metrics = next_token_loss(
            logits, batch["tokens"], batch.get("loss_mask")
        )
        # what the model makes of what its pass sowed: a term of the loss and
        # counters among the step's metrics
        aux_penalty, counters = self.model_cfg.sown_readings(collections)
        return loss + aux_penalty, dict(metrics, **counters)

    def _train_step(self, state: TrainState, batch: dict):
        dropout_rng = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed), state.step)
        grad_fn = jax.value_and_grad(self._loss_fn, has_aux=True)
        accum = self.cfg.grad_accum_steps
        if accum > 1:
            # microbatch scan: rows stay sharded over the batch axes within
            # each microbatch; the accum axis is sequential. Grads/metrics
            # are averaged over microbatches — identical semantics to the
            # unsplit step (each microbatch's loss is already a per-token
            # mean, so equality is exact only for uniform token counts; SFT
            # masks make it the standard per-microbatch-mean approximation).
            def split(x):
                r = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                # the scan (accum) axis must stay UNSHARDED — it is
                # sequential; rows keep their batch-axis sharding within
                # each microbatch
                spec = self._batch_leaf_sharding(x).spec
                return jax.lax.with_sharding_constraint(
                    r, NamedSharding(self.mesh, P(None, *spec))
                )

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                rng = jax.random.fold_in(dropout_rng, carry["i"])
                (_, aux), grads = grad_fn(state.trainable, state.frozen, mb, rng)
                acc = jax.tree.map(jnp.add, carry["grads"], grads)
                auxes = jax.tree.map(jnp.add, carry["aux"], aux)
                return {"grads": acc, "aux": auxes, "i": carry["i"] + 1}, None

            zero_grads = jax.tree.map(jnp.zeros_like, state.trainable)
            aux_shape = jax.eval_shape(
                lambda: grad_fn(
                    state.trainable, state.frozen,
                    jax.tree.map(lambda x: x[0], micro), dropout_rng,
                )[0][1]
            )
            zero_aux = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), aux_shape
            )
            with jax.named_scope("grad_accum"):
                carry, _ = jax.lax.scan(
                    body,
                    {"grads": zero_grads, "aux": zero_aux,
                     "i": jnp.zeros((), jnp.int32)},
                    micro,
                )
            inv = 1.0 / accum
            grads = jax.tree.map(lambda g: g * inv, carry["grads"])
            # means average over microbatches; counts keep their exact sum
            aux = {
                k: (v if k == "target_tokens" else v * inv)
                for k, v in carry["aux"].items()
            }
        else:
            (_, aux), grads = grad_fn(state.trainable, state.frozen, batch, dropout_rng)
        with jax.named_scope("optimizer"):  # clip included: it is in tx
            updates, opt_state = self.tx.update(grads, state.opt_state, state.trainable)
            trainable = optax.apply_updates(state.trainable, updates)
        metrics = {
            **aux,
            "grad_norm": optax.global_norm(grads),
            "learning_rate": self.sched(state.step),
        }
        new_state = state.replace(
            step=state.step + 1, trainable=trainable, opt_state=opt_state
        )
        return new_state, metrics

    def _eval_step(self, state: TrainState, batch: dict):
        """Forward-only loss/accuracy on a held-out batch (no grads, no
        state mutation — dropout off regardless of training mode)."""
        variables = self._assemble(state.frozen, state.trainable)
        apply_kw: dict[str, Any] = dict(
            segment_ids=batch.get("segment_ids"), deterministic=True,
        )
        if self._is_multimodal:
            apply_kw["pixels"] = batch.get("pixels")
        sown = self.model_cfg.sown_in_eval
        if sown:
            logits, _ = self.model.apply(
                variables, batch["tokens"], mutable=sown, **apply_kw)
        else:
            logits = self.model.apply(variables, batch["tokens"], **apply_kw)
        _, metrics = next_token_loss(
            logits, batch["tokens"], batch.get("loss_mask")
        )
        return metrics

    def _get_eval_jit(self, batch: dict):
        key = ("eval",) + tuple(sorted(batch))
        fn = self._step_jits.get(key)
        if fn is None:
            batch_sh = {k: self._batch_leaf_sharding(batch[k]) for k in batch}
            fn = jax.jit(
                self._eval_step,
                in_shardings=(self._state_shardings, batch_sh),
                out_shardings=None,
            )
            obs_trace.STARTUP.step_program(self._eval_step.__name__)
            if self._recompile_guard is not None:
                fn = self._recompile_guard.wrap(fn, label=f"eval:{','.join(key)}")
            self._step_jits[key] = fn
        return fn

    def evaluate(self, state: TrainState, eval_batches: Iterator[dict]) -> dict:
        """Average forward-only metrics over ``cfg.eval_steps`` batches."""
        from ..parallel.ring import ring_mesh

        sums: dict[str, float] = {}
        n = 0
        n_batches = max(1, self.cfg.eval_steps)
        input_s = 0.0  # host build + transfer time the eval pass waited on
        for _ in range(n_batches):
            t_in = time.perf_counter()
            host_batch = next(eval_batches)
            input_s += time.perf_counter() - t_in
            # grad accumulation exists because the full batch's activations
            # don't fit HBM — eval must microbatch the same way or it OOMs
            # at the first eval step of exactly those configs
            accum = self.cfg.grad_accum_steps
            rows = next(iter(host_batch.values())).shape[0]
            chunks = accum if accum > 1 and rows % accum == 0 else 1
            if accum > 1 and chunks == 1 and not self._warned_eval_unsplit:
                # per-host rows not divisible: the unsplit eval forward may
                # not fit HBM on exactly the configs accumulation targets
                self._warned_eval_unsplit = True
                logger.warning(
                    "eval batch rows (%d per host) not divisible by "
                    "grad_accum_steps (%d): evaluating UNSPLIT — if this "
                    "OOMs, make batch_size/process_count divisible by "
                    "grad_accum_steps", rows, accum,
                )
            for c in range(chunks):
                t_in = time.perf_counter()
                piece = {
                    k: v[c * (rows // chunks):(c + 1) * (rows // chunks)]
                    for k, v in host_batch.items()
                }
                batch = self.shard_batch(piece)
                input_s += time.perf_counter() - t_in
                fn = self._get_eval_jit(batch)
                with self.mesh, ring_mesh(self.mesh):
                    metrics = fn(state, batch)
                for k, v in metrics.items():
                    sums[k] = sums.get(k, 0.0) + float(v)
                n += 1
            hb = getattr(self, "_heartbeat", None)
            if hb is not None:
                # liveness through a long eval pass (the per-batch float()
                # above already synced the device, so this reads step cheaply)
                hb.beat(int(state.step))
        # target_tokens is a per-batch count — averaging it is meaningless,
        # and only declared columns survive the CSV header
        out = {
            f"eval_{k}": v / n for k, v in sums.items() if k != "target_tokens"
        }
        # input-pipeline observability: host build + transfer time per eval
        # batch (ms) — an input-bound eval shows up here, not in eval_loss
        out["eval_input_ms"] = input_s / n_batches * 1000.0
        return out

    # ---- host-side API ---------------------------------------------------

    def init_state(self) -> TrainState:
        from ..parallel.ring import ring_mesh

        with self.mesh, ring_mesh(self.mesh):
            return self._init_jit(jax.random.PRNGKey(self.cfg.seed))

    def step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        log = obs_trace.STARTUP
        if log.closed:
            return self._step(state, batch)
        # the process's first step: shard the batch, trace, lower, compile or
        # load, enqueue — and its return ends the start-up log
        try:
            with log.span("trainer.first_step") as span:
                out = self._step(state, batch)
                span["attributes"]["step_programs"] = len(self._step_jits)
                return out
        finally:
            log.close()

    def _step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        from ..parallel.ring import ring_mesh

        # numbered on the host: reading state.step would wait for the device
        self._steps_enqueued += 1
        with annotate("trainer.step", step_num=self._steps_enqueued):
            with annotate("trainer.shard_batch"):
                batch = self.shard_batch(batch)
            step_fn = self._get_step_jit(batch)
            # ring_mesh only matters at trace time (first call); harmless after
            with self.mesh, ring_mesh(self.mesh), annotate("trainer.enqueue"):
                return step_fn(state, batch)

    @property
    def local_batch_size(self) -> int:
        """Rows each process's data pipeline must supply per step.

        ``cfg.batch_size`` is the GLOBAL batch; on a multi-host slice each
        host loads only its share and the global array is assembled from
        per-process shards (no cross-host row duplication or waste).
        """
        n = jax.process_count()
        if self.cfg.batch_size % n:
            raise ValueError(
                f"global batch_size {self.cfg.batch_size} not divisible by "
                f"process count {n}"
            )
        return self.cfg.batch_size // n

    def shard_batch(self, batch: dict) -> dict:
        """The batch on the device(s) with the step's input shardings (an
        async ``device_put``; leaves already placed so are passed through) —
        what ``step`` does first, and what a prefetch thread does ahead."""
        def put(x):
            if isinstance(x, jax.Array):
                # already transferred (the prefetch pipeline device_puts with
                # these same shardings on its own thread) — a np.asarray here
                # would copy the batch BACK to host and resubmit it
                if x.sharding == self._batch_leaf_sharding(x):
                    return x
                return jax.device_put(x, self._batch_leaf_sharding(x))
            x = np.asarray(x)
            sh = self._batch_leaf_sharding(x)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        return jax.tree.map(put, batch)

    _shard_batch = shard_batch

    def load_pretrained(self, state: TrainState, ckpt_dir: str) -> TrainState:
        """Replace the base-model weights with a pretrained HF checkpoint
        (``models/hf_import.py``), resharded onto the state's shardings.

        LoRA/QLoRA modes load into the frozen ``params`` collection (int4
        kernels are quantized on the way in); full fine-tune loads into the
        trainable tree. The loaded tree is shape-checked leaf-by-leaf against
        the initialised state so a config mismatch fails loudly.

        Multimodal (LLaVA): the checkpoint's vision tower + language model
        fill the frozen base, and the projector fills its slot in the
        TRAINABLE tree (the LLaVA recipe always trains the projector)."""
        quant_block = getattr(self.model_cfg, "quant_block", None) or (
            self.model_cfg.text.quant_block if self._is_multimodal else 64
        )
        if self._is_multimodal:
            from ..models.hf_import import load_llava_params

            loaded = load_llava_params(ckpt_dir, self.model_cfg)
        else:
            from ..models.hf_import import load_llama_params

            loaded = load_llama_params(ckpt_dir, self.model_cfg)
        if self.cfg.mode != "lora":
            adapted = _adapt_loaded_params(
                loaded, state.trainable, quant_block=quant_block
            )
            adapted = reshard(adapted, self._state_shardings.trainable)
            return state.replace(trainable=adapted)
        if self._is_multimodal:
            proj_loaded = {
                k: loaded.pop(k) for k in self._MM_TRAINED_PARAMS if k in loaded
            }
            proj = _adapt_loaded_params(
                proj_loaded, state.trainable["projector"],
                quant_block=quant_block,
            )
            proj = reshard(proj, self._state_shardings.trainable["projector"])
            trainable = dict(state.trainable)
            trainable["projector"] = proj
        else:
            trainable = None
        adapted = _adapt_loaded_params(
            loaded, state.frozen["params"], quant_block=quant_block
        )
        adapted = reshard(adapted, self._state_shardings.frozen["params"])
        frozen = dict(state.frozen)
        frozen["params"] = adapted
        state = state.replace(frozen=frozen)
        if trainable is not None:
            state = state.replace(trainable=trainable)
        return state

    def export_artifacts(
        self,
        state: TrainState,
        artifacts_dir: str,
        pretrained_dir: str | None = None,
    ) -> None:
        """Write deployable HF-format artifacts after training: a PEFT
        adapter for text LoRA runs, plus a merged checkpoint when
        ``cfg.export_merged``. Collective (all hosts gather), rank 0 writes.

        ``pretrained_dir`` (the job's base checkpoint) enables the merged
        export on MULTI-HOST meshes: the sharded frozen base spans
        non-addressable devices, so instead of an expensive cross-host gather
        of GBs of frozen weights, rank 0 reloads the base host-side from the
        original safetensors and merges the already-gathered adapter into it
        (reference promotion contract: ``app/tasks/promotion.py:11-38`` — a
        deployable artifact for every job type).

        Multimodal LoRA runs export the decoder adapter (PEFT format, keyed
        under ``language_model`` — HF LLaVA's layout) plus the trained
        projector (``adapter/projector.safetensors``); merged multimodal
        export is out of scope (the tower/projector/decoder split has no
        single-file HF form a text merge could produce)."""
        if self.cfg.mode != "lora":
            return
        scan = (
            self.model_cfg.text.scan_layers if self._is_multimodal
            else self.model_cfg.scan_layers
        )
        if not scan:
            logger.warning(
                "HF adapter export supports the scanned layer layout only "
                "(scan_layers=False run): skipping export"
            )
            return
        # collective — every rank calls; only the adapter slice is gathered
        host = self.state_to_host(state, fields=("trainable",))
        if jax.process_index() != 0:
            return
        from ..models.hf_export import (
            export_lora_adapter,
            export_merged_checkpoint,
            export_mm_projector,
        )

        if self._is_multimodal:
            export_lora_adapter(
                self.model_cfg.text, host["trainable"]["lora"],
                f"{artifacts_dir}/adapter",
                hf_prefix="base_model.model.language_model.model.layers",
            )
            export_mm_projector(
                host["trainable"]["projector"], f"{artifacts_dir}/adapter"
            )
            if self.cfg.export_merged:
                logger.warning(
                    "export_merged skipped: multimodal runs export the "
                    "adapter + projector (no single-file HF merge exists)"
                )
            return
        export_lora_adapter(
            self.model_cfg, host["trainable"], f"{artifacts_dir}/adapter"
        )
        if self.cfg.export_merged:
            if jax.process_count() > 1:
                if not pretrained_dir:
                    # random-init multi-host run (smoke/proxy): nothing to
                    # reload host-side; merge offline from the adapter
                    logger.warning(
                        "export_merged skipped on multi-host: no pretrained "
                        "base directory to reload host-side; merge offline "
                        "from the adapter and the base"
                    )
                    return
                from ..models.hf_import import load_llama_params

                loaded = load_llama_params(pretrained_dir, self.model_cfg)
                # QLoRA faithfulness: the adapter trained against the
                # QUANTIZED base — re-apply the same int4 adaptation (against
                # eval_shape targets, so no device memory is touched) so the
                # merged weights are deq(Q(W)) + delta, matching the
                # single-host path's dequantized frozen leaves
                shapes = jax.eval_shape(
                    self.raw_init, jax.random.PRNGKey(self.cfg.seed)
                )
                loaded = _adapt_loaded_params(
                    loaded, shapes.frozen["params"],
                    quant_block=self.model_cfg.quant_block,
                )
                frozen_host: dict = {"params": loaded}
            else:
                frozen_host = jax.tree.map(
                    lambda x: np.asarray(jax.device_get(x)), dict(state.frozen)
                )
            variables = self._assemble(frozen_host, host["trainable"])
            try:
                export_merged_checkpoint(
                    self.model_cfg, variables, f"{artifacts_dir}/merged"
                )
            except NotImplementedError as exc:
                # an unsupported merged layout (partial Gemma semantics) must
                # not fail a completed run — the adapter already shipped
                logger.warning("export_merged skipped: %s", exc)

    def state_to_host(
        self,
        state: TrainState,
        fields: tuple[str, ...] = ("step", "trainable", "opt_state"),
    ) -> dict:
        """Gather the persistable slice of state (trainable + opt) to host.

        On a multi-host mesh, sharded arrays span non-addressable devices and
        plain ``device_get`` raises; every process must participate in a
        collective gather (all hosts call this, only rank 0 persists).
        ``fields`` narrows the gather (e.g. adapter export needs only
        ``trainable`` — no point allgathering Adam moments for it).
        """
        tree = {f: getattr(state, f) for f in fields}
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            tree = multihost_utils.process_allgather(tree, tiled=True)
        return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)

    @property
    def mesh_axes(self) -> dict[str, int]:
        """Live mesh axis sizes (``{"dp": 2, "fsdp": 1, ...}``) — what the
        checkpoint manifest records and elastic restore compares against."""
        return {
            name: int(size)
            for name, size in zip(self.mesh.axis_names, self.mesh.devices.shape)
        }

    def _build_manifest(self, step: int, host_state: dict) -> dict:
        from .elastic import build_manifest

        return build_manifest(
            step=step,
            mesh_axes=self.mesh_axes,
            rule_fingerprint=self._rule_fingerprint,
            global_batch_size=self.cfg.batch_size,
            grad_accum_steps=self.cfg.grad_accum_steps,
            seq_len=self.cfg.seq_len,
            seed=self.cfg.seed,
            host_tree=host_state,
        )

    def _plan_elastic_resume(self, ckpt: CheckpointManager, latest: int,
                             multi: bool) -> None:
        """Cross-topology resume contract (``train/elastic.py``): verify the
        checkpoint's partition-rule fingerprint against the live rule table
        and recompute ``grad_accum_steps`` so the global batch decomposes
        into the same row-shards on the live mesh.  Legacy (manifest-less)
        checkpoints restore as before — same-shape only.

        Multi-host: the manifest lives on rank 0's storage; rank 0 plans and
        the outcome (or the refusal) is broadcast so every host mutates its
        config identically — divergent ``grad_accum_steps`` would compile
        different step graphs and deadlock on collectives.
        """
        from .elastic import (
            ElasticManifestError,
            check_fingerprint,
            plan_elastic_resume,
        )

        plan = None
        error: str | None = None
        if not multi or jax.process_index() == 0:
            manifest = ckpt.load_manifest(latest)
            if manifest is not None:
                try:
                    check_fingerprint(manifest, self._rule_fingerprint)
                    plan = plan_elastic_resume(
                        manifest,
                        self.mesh_axes,
                        batch_size=self.cfg.batch_size,
                        grad_accum_steps=self.cfg.grad_accum_steps,
                    )
                except ElasticManifestError as exc:
                    error = str(exc)
        if multi:
            from jax.experimental import multihost_utils

            # (-2 = refusal, -1 = no manifest, >=1 = planned accumulation)
            code = -2 if error else (-1 if plan is None else plan.grad_accum_steps)
            code = int(multihost_utils.broadcast_one_to_all(
                np.asarray(code, np.int64)
            ))
            if code == -2:
                raise ElasticManifestError(
                    error or "rank 0 refused the checkpoint manifest"
                )
            if code >= 1 and plan is None:
                # non-zero rank: apply rank 0's planned accumulation
                self.cfg.grad_accum_steps = code
                return
        if error:
            raise ElasticManifestError(error)
        if plan is None:
            return
        if plan.topology_changed or plan.grad_accum_steps != self.cfg.grad_accum_steps:
            logger.info(
                "elastic restore: checkpoint mesh %s -> live mesh %s "
                "(grad_accum_steps %d -> %d, batch shards %s)",
                plan.source_axes, plan.target_axes,
                self.cfg.grad_accum_steps, plan.grad_accum_steps,
                "preserved" if plan.microstructure_preserved else "re-decomposed",
            )
        self.cfg.grad_accum_steps = plan.grad_accum_steps

    def _writer_extra_fields(self, eval_enabled: bool) -> tuple[str, ...]:
        """Metrics-CSV columns that may appear only on later rows and must be
        declared up front (``MetricsWriter`` pins the header at first write).
        Subclass hook: ``prefs.dpo_trainer.DPOTrainer`` adds its eval and
        rollout columns here."""
        fields: tuple[str, ...] = ("input_ms", "input_fraction")
        if eval_enabled:
            fields += ("eval_loss", "eval_accuracy", "eval_input_ms")
        return fields

    def _row_extras(self) -> dict:
        """Host-side metrics merged into every logged row (subclass hook —
        the rlhf learner reports rollout-buffer depth/staleness and actor
        throughput through this)."""
        return {}

    @staticmethod
    def _consume_profile_request(path: str) -> int:
        """Read + retire an on-demand profiler request delivered through the
        artifact channel (``POST /jobs/{id}/profile`` →
        ``backend.deliver_file`` → ``profile_request.json``).  Returns the
        requested step count (0 = unreadable).  The file is renamed either
        way so a bad payload can't re-trigger every step."""
        try:
            with open(path) as f:
                doc = json.load(f)
            steps = max(1, min(int(doc.get("steps", 5)), 1000))
        except (OSError, ValueError, TypeError):
            steps = 0
        try:
            os.replace(path, path + ".consumed")
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass
        return steps

    @staticmethod
    def _sync_preemption(local_flag: bool) -> bool:
        """OR a per-host preemption flag across all hosts (one tiny allgather
        per step — negligible next to a training step, and required so every
        host takes the same checkpoint/exit branch)."""
        if jax.process_count() == 1:
            return local_flag
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray(local_flag, np.bool_), tiled=False
        )
        return bool(np.any(flags))

    def fit(
        self,
        batches: Iterable[dict],
        artifacts_dir: str,
        resume: bool = True,
        on_metrics: Callable[[int, dict], None] | None = None,
        pretrained_dir: str | None = None,
        eval_batches: Iterable[dict] | None = None,
    ) -> TrainState:
        guard = PreemptionGuard()
        try:
            guard.install()
        except ValueError:
            pass  # not on the main thread (e.g. tests)
        # Preemption-flag sync cadence: bounded by log cadence so detection
        # latency stays low without paying a cross-host sync every step.
        self._preempt_sync_every = max(1, min(self.cfg.log_every, self.cfg.checkpoint_every))

        ckpt = CheckpointManager(
            f"{artifacts_dir}/checkpoints", keep=self.cfg.keep_checkpoints
        )
        # observability (docs/observability.md): rank 0 records lifecycle
        # events (events.jsonl) + spans (trace/trainer.jsonl) through the
        # artifact channel, and the phase clock splits every logging window
        # into input/compute/checkpoint/sync/eval.  FTC_TRACE=0 is the
        # operator kill switch.
        from ..obs.events import EventLogWriter
        from ..obs.phase import PhaseClock
        from ..obs.trace import SpanRecorder

        obs_on = (
            self.cfg.trace
            and os.environ.get("FTC_TRACE", "1").strip().lower()
            not in ("0", "false", "no", "off")
            and jax.process_index() == 0
        )
        # on-demand profiling is deliberately NOT gated on tracing: with
        # FTC_TRACE=0 an operator can still arm a jax.profiler window on a
        # live job (otherwise POST /jobs/{id}/profile 202s into a request
        # file nothing ever reads).  FTC_PROFILE=0 is its own kill switch.
        profile_poll_on = (
            os.environ.get("FTC_PROFILE", "1").strip().lower()
            not in ("0", "false", "no", "off")
            and jax.process_index() == 0
        )
        trace_id = os.environ.get("FTC_TRACE_ID", "")
        obs_attempt = int(os.environ.get("FTC_ATTEMPT", "1") or 1)
        events_log = EventLogWriter(
            artifacts_dir, trace_id=trace_id, attempt=obs_attempt,
            enabled=obs_on,
        )

        def checkpoint_committed(step: int, blocking: bool) -> None:
            # the WRITER thread's to call, once its save is on disk: a job's
            # first save imports the checkpoint library on that thread
            # (``CheckpointManager._ckptr``), and what that cost rides the
            # first event (0.0 on every later one)
            events_log.emit(
                "checkpoint-committed", step=step, blocking=blocking or None,
                **ckpt.take_backend_import(),
            )

        # the recorder adopts the process's start-up log: what ran before
        # fit() — imports, the backend, Trainer() — lands under ``fit``
        spans = SpanRecorder(
            artifacts_dir, trace_id, attempt=obs_attempt, enabled=obs_on,
            startup=obs_trace.STARTUP,
        )
        phases = PhaseClock()
        fit_span = spans.start("fit", total_steps=self.cfg.total_steps)
        with spans.span("init", parent=fit_span):
            state = self.init_state()
        start_step = 0
        latest = None
        multi = jax.process_count() > 1
        if resume:
            latest = ckpt.latest_step()
            if multi:
                # All hosts must agree on the resume decision: artifacts_dir may
                # be host-local storage where only rank 0 persisted, so rank 0's
                # view is authoritative. Without this broadcast, hosts would run
                # different numbers of jitted steps and deadlock on collectives.
                from jax.experimental import multihost_utils

                latest_arr = multihost_utils.broadcast_one_to_all(
                    np.asarray(-1 if latest is None else latest, np.int64)
                )
                latest = None if int(latest_arr) < 0 else int(latest_arr)
        if pretrained_dir and not (latest is not None and self.cfg.mode == "full"):
            # pretrained base before the checkpoint restore: the restore only
            # replaces the trainable/optimizer slice, so in LoRA/QLoRA mode
            # the base weights must come from here even on resume. In full
            # fine-tune the checkpoint holds everything — reloading GBs of
            # safetensors just to overwrite them would waste every resume.
            state = self.load_pretrained(state, pretrained_dir)
        restore_span = (
            spans.start("restore", parent=fit_span, step=latest)
            if resume and latest is not None else None
        )
        if resume:
            if latest is not None:
                # Topology-portable resume (train/elastic.py): verify the
                # manifest and recompute the batch microstructure BEFORE any
                # step function traces — the state itself is host-gathered
                # full arrays, so the reshard below lands it on whatever
                # mesh is live now.
                self._plan_elastic_resume(ckpt, latest, multi)
                # Only rank 0 is guaranteed to hold the checkpoint bytes, so
                # rank 0 restores and the tree is broadcast; other hosts feed
                # the broadcast a structure-matching template.
                template = self.state_to_host(state)
                if not multi or jax.process_index() == 0:
                    host = ckpt.restore(latest, like=template)
                else:
                    host = template
                if multi:
                    host = multihost_utils.broadcast_one_to_all(host)
                state = state.replace(
                    # step rides reshard too: a bare jnp.asarray commits it
                    # to one default device, not the mesh-replicated spec
                    # the rule table (and the shard audit) expect
                    step=reshard(
                        jnp.asarray(host["step"], jnp.int32),
                        self._state_shardings.step,
                    ),
                    trainable=reshard(host["trainable"], self._state_shardings.trainable),
                    opt_state=reshard(host["opt_state"], self._state_shardings.opt_state),
                )
                self._audit_state_sharding(state, "restore")
                start_step = int(host["step"])
                spans.finish(restore_span, step=start_step,
                             **ckpt.take_backend_import())
                logger.info("resumed from checkpoint step %d", start_step)

        # liveness heartbeat (resilience/heartbeat.py): rank 0 proves forward
        # progress through the artifact channel; the monitor's lease check
        # kills + requeues a job whose heartbeat goes stale
        heartbeat = None
        if self.cfg.heartbeat_interval_s > 0 and jax.process_index() == 0:
            from ..resilience.heartbeat import HeartbeatWriter

            heartbeat = HeartbeatWriter(
                artifacts_dir, interval_s=self.cfg.heartbeat_interval_s
            )
            heartbeat.beat(start_step, force=True)
        # evaluate() beats through this handle — an eval pass over many
        # batches must not look like a stall to the liveness lease
        self._heartbeat = heartbeat
        events_log.emit(
            "train-started", step=start_step,
            resumed_from=start_step if start_step else None,
            startup_s=obs_trace.STARTUP.summary(),
            **self._runtime_attrs(),
        )
        # chaos hook (resilience/faults.py): a seeded kill-at-step armed via
        # FTC_FAULT_* env vars — None outside fault-injection runs
        from ..resilience.faults import StepFaultInjector

        fault = StepFaultInjector.from_env()

        eval_it: Iterator[dict] | None = (
            iter(eval_batches) if eval_batches is not None else None
        )
        if self.cfg.eval_every > 0 and eval_it is None:
            raise ValueError(
                "eval_every > 0 but no eval_batches were supplied to fit()"
            )
        # input_ms/input_fraction ride every logged row, but must ALSO be
        # declared so a resume appending to a pre-input-metrics CSV rewrites
        # the header union instead of silently dropping the new columns
        writer = MetricsWriter(
            artifacts_dir, append=start_step > 0,
            extra_fields=self._writer_extra_fields(eval_it is not None)
            + (PhaseClock.columns() if obs_on else ()),
            # a crash AFTER a logged row but BEFORE its checkpoint committed
            # makes this run replay those steps — drop their rows so the
            # replay doesn't duplicate them
            resume_step=start_step,
        )
        it: Iterator[dict] = iter(batches)
        # Fast-forward past already-consumed batches so a resumed run sees the
        # same data stream an uninterrupted run would have. This happens on
        # the RAW iterator, before the prefetch wrap — the skip loop and the
        # prefetch producer must never race for batches.
        for _ in range(start_step):
            next(it)
        prefetch_its: list[Any] = []
        if self.cfg.prefetch > 0:
            from ..data.prefetch import PrefetchIterator

            # the producer thread builds batch N+1..N+k while the device runs
            # step N; the transfer stage additionally device_puts the next
            # batch with the step's shardings (async dispatch → the host→HBM
            # copy overlaps compute, double-buffered by the queue)
            it = PrefetchIterator(
                it, depth=self.cfg.prefetch,
                transfer=self.shard_batch if self.cfg.prefetch_to_device else None,
            )
            prefetch_its.append(it)
            if eval_it is not None and self.cfg.eval_every > 0:
                # eval_every == 0 means evaluate() never runs — don't spin a
                # producer that eagerly builds eval batches nobody consumes
                eval_it = PrefetchIterator(eval_it, depth=1)
                prefetch_its.append(eval_it)
        tokens_per_batch = self.cfg.batch_size * self.cfg.seq_len
        window_t0 = time.perf_counter()
        window_tokens = 0
        # input-pipeline observability: host time each step actually WAITED
        # for its batch (with prefetch on this is the residual stall, not the
        # overlapped build time — a healthy pipeline logs input_fraction ~0)
        window_input_s = 0.0
        window_steps = 0
        # jax.profiler trace window (rank 0 only): ships with the artifacts
        profiling = False
        prof_first = start_step + self.cfg.profile_start_step
        want_profile = self.cfg.profile_steps > 0 and jax.process_index() == 0
        if want_profile and start_step >= self.cfg.total_steps:
            # resumed past the end: no step will run, so no trace can exist
            logger.warning(
                "profiling requested but the run is already complete "
                "(resumed at step %d of %d); no trace will be captured",
                start_step, self.cfg.total_steps,
            )
            want_profile = False
        elif want_profile and prof_first >= self.cfg.total_steps:
            # a requested trace must never silently no-op: clamp the window
            # to the run instead of skipping it
            logger.warning(
                "profile_start_step %d is past the run (total_steps %d); "
                "profiling from the first step instead",
                self.cfg.profile_start_step, self.cfg.total_steps,
            )
            prof_first = start_step
        prof_last = prof_first + self.cfg.profile_steps  # exclusive
        prof_start_actual = prof_first  # where the live window really began
        # on-demand profiler window (docs/observability.md): the controller
        # delivers profile_request.json through the artifact channel and the
        # loop picks it up within one poll window — a live job profiles
        # without restarting.  The stat() is throttled to the preemption-sync
        # cadence: per-step filesystem polling has no place in the step loop.
        profile_req_path = os.path.join(artifacts_dir, "profile_request.json")
        profile_poll = self._preempt_sync_every
        try:
            for step_idx in range(start_step, self.cfg.total_steps):
                iter_t0 = time.perf_counter()
                if want_profile and not profiling and step_idx >= prof_first:
                    # >= not ==: an on-demand window may span the configured
                    # start step — the configured trace then begins at the
                    # first free step instead of silently never firing (and
                    # never having its end marker clobbered)
                    jax.profiler.start_trace(f"{artifacts_dir}/profile")
                    profiling = True
                    want_profile = False  # one configured window per run
                    prof_start_actual = step_idx
                    # clamp to the run so the in-loop stop (and its
                    # profile-captured confirmation) always fires — the
                    # finally-block stop_trace is a silent flush
                    prof_last = min(
                        step_idx + self.cfg.profile_steps,
                        self.cfg.total_steps,
                    )
                if (
                    profile_poll_on and not profiling
                    and step_idx % profile_poll == 0
                    and os.path.exists(profile_req_path)
                ):
                    steps_req = self._consume_profile_request(profile_req_path)
                    if steps_req:
                        jax.profiler.start_trace(f"{artifacts_dir}/profile")
                        profiling = True
                        prof_start_actual = step_idx
                        prof_last = min(
                            step_idx + steps_req, self.cfg.total_steps
                        )
                t_in = time.perf_counter()
                batch = next(it)
                dt_in = time.perf_counter() - t_in
                window_input_s += dt_in
                if obs_on:
                    phases.add("input", dt_in)
                window_steps += 1
                state, metrics = self.step(state, batch)
                window_tokens += tokens_per_batch
                if heartbeat is not None:
                    t_hb = time.perf_counter()
                    heartbeat.beat(
                        step_idx + 1,
                        step_ms=(t_hb - iter_t0) * 1000.0,
                    )
                    if obs_on:
                        phases.add("sync", time.perf_counter() - t_hb)
                if fault is not None:
                    # after the step so a SIGTERM's save reflects real progress
                    fault.maybe_fire(step_idx + 1)
                if profiling and step_idx + 1 >= prof_last:
                    jax.block_until_ready(state)
                    jax.profiler.stop_trace()
                    profiling = False
                    # force: profiling is decoupled from the tracing kill
                    # switch, so its confirmation must be too — the
                    # timeline otherwise shows a request with no capture
                    events_log.emit(
                        "profile-captured", step=step_idx + 1, force=True
                    )
                    logger.info(
                        "profiler trace for steps [%d, %d) -> %s/profile",
                        prof_start_actual, prof_last, artifacts_dir,
                    )

                last = step_idx + 1 == self.cfg.total_steps
                eval_now = (
                    self.cfg.eval_every > 0
                    and eval_it is not None
                    and ((step_idx + 1) % self.cfg.eval_every == 0 or last)
                )
                eval_metrics: dict[str, float] = {}
                eval_elapsed = 0.0
                if eval_now:
                    eval_t0 = time.perf_counter()
                    with spans.span("eval", parent=fit_span, step=step_idx + 1):
                        eval_metrics = self.evaluate(state, eval_it)
                    eval_elapsed = time.perf_counter() - eval_t0
                    if obs_on:
                        phases.add("eval", eval_elapsed)
                    logger.info(
                        "step %d eval_loss %.4f eval_acc %.3f",
                        step_idx + 1, eval_metrics["eval_loss"],
                        eval_metrics["eval_accuracy"],
                    )
                # eval metrics ride ON a train log row (eval steps force one)
                # so the CSV stays dense within each written row
                if (step_idx + 1) % self.cfg.log_every == 0 or last or eval_now:
                    with annotate("trainer.log_sync"):
                        # float() waits for the step that produced the row
                        metrics = {k: float(v) for k, v in metrics.items()}
                    # the evaluation pause doesn't count against throughput
                    dt = time.perf_counter() - window_t0 - eval_elapsed
                    metrics["tokens_per_sec"] = window_tokens / max(dt, 1e-9)
                    # input-time share of the window: near 0 = device-bound
                    # (healthy); toward 1 = input-bound (grow prefetch depth
                    # or move host work off the loader)
                    metrics["input_ms"] = (
                        window_input_s / max(window_steps, 1) * 1000.0
                    )
                    metrics["input_fraction"] = window_input_s / max(dt, 1e-9)
                    metrics.update(eval_metrics)
                    if obs_on:
                        # step-phase split (docs/observability.md): per-step
                        # averages over the FULL window wall (eval included —
                        # it is one of the phases)
                        metrics.update(phases.window_row(
                            steps=window_steps, wall_s=dt + eval_elapsed
                        ))
                    metrics.update(self._row_extras())
                    row = {"step": step_idx + 1, **metrics}
                    writer.write(row)
                    if on_metrics:
                        on_metrics(step_idx + 1, metrics)
                    logger.info(
                        "step %d loss %.4f acc %.3f tok/s %.0f input %.1fms"
                        " (%.1f%% of step)",
                        step_idx + 1, metrics["loss"], metrics["accuracy"],
                        metrics["tokens_per_sec"], metrics["input_ms"],
                        100.0 * metrics["input_fraction"],
                    )
                    window_t0 = time.perf_counter()
                    window_tokens = 0
                    window_input_s = 0.0
                    window_steps = 0

                # SIGTERM may reach only some hosts; state_to_host is a
                # collective, so the preempt flag must be agreed across hosts
                # (any-host OR) before any host enters the gather. The sync is
                # a blocking allgather that would serialize host and device if
                # run every step, so it only runs on a deterministic cadence
                # (same arithmetic on every host ⇒ still collective-safe).
                sync_now = (
                    (step_idx + 1) % self._preempt_sync_every == 0
                    or (step_idx + 1) % self.cfg.checkpoint_every == 0
                    or last
                )
                t_sync = time.perf_counter()
                preempt = self._sync_preemption(guard.requested) if sync_now else False
                if obs_on and sync_now:
                    phases.add("sync", time.perf_counter() - t_sync)
                if (step_idx + 1) % self.cfg.checkpoint_every == 0 or last or preempt:
                    blocking_save = last or preempt or self._blocking_checkpoints
                    ck_span = spans.start(
                        "checkpoint", parent=fit_span, step=step_idx + 1,
                        blocking=blocking_save,
                    )
                    t_ck = time.perf_counter()
                    # A checkpoint of mis-sharded state would round-trip the
                    # damage through every later restore — audit BEFORE the
                    # host gather flattens the evidence away.
                    self._audit_state_sharding(state, f"checkpoint:{step_idx + 1}")
                    # Collective gather on all hosts; rank 0 persists.
                    host_state = self.state_to_host(state)
                    if jax.process_index() == 0:
                        # Mid-run saves overlap the next steps (the goodput
                        # lever); the LAST save has nothing left to overlap
                        # with — commit it synchronously so no background
                        # save thread races the teardown below (prefetch
                        # close / profiler stop), a race observed as a rare
                        # interpreter crash on fast CPU test runs.  Every
                        # committed checkpoint carries its topology manifest
                        # (train/elastic.py) so ANY later mesh can restore it.
                        ckpt.save(
                            step_idx + 1, host_state, blocking=blocking_save,
                            manifest=self._build_manifest(
                                step_idx + 1, host_state),
                            on_commit=partial(
                                checkpoint_committed, step_idx + 1,
                                blocking_save),
                        )
                    if obs_on:
                        # the host-side cost of this save (gather + write for
                        # a blocking save; gather + handoff for an async one)
                        phases.add("checkpoint", time.perf_counter() - t_ck)
                    spans.finish(ck_span)
                if preempt:
                    logger.warning("exiting on preemption after step %d", step_idx + 1)
                    events_log.emit("preempt-exit", step=step_idx + 1)
                    raise SystemExit(143)
        finally:
            self._heartbeat = None  # evaluate() outside fit must not beat
            # stop the prefetch producers FIRST: a producer mid-build must
            # not keep decoding images while teardown waits on checkpoints
            for p in prefetch_its:
                p.close()
            if profiling:
                jax.profiler.stop_trace()
            # Must be read before the inner except handler runs: inside an
            # except block sys.exc_info() reports the just-caught exception,
            # which would make a wait() failure always look "propagating".
            propagating = sys.exc_info()[1] is not None
            try:
                # durability barrier: an async checkpoint save must commit
                # before the process exits (especially the preemption path —
                # the point of the save-on-SIGTERM is surviving the kill)
                ckpt.wait()
            except Exception:
                if propagating:
                    # an exception (e.g. the preemption SystemExit 143) is
                    # already propagating: log the save failure rather than
                    # masking the original exit semantics
                    logger.exception("final checkpoint save failed during teardown")
                else:
                    raise
            finally:
                writer.close()
                spans.finish(
                    fit_span, status="error" if propagating else "ok",
                    start_step=start_step,
                )
                if not propagating:
                    events_log.emit(
                        "train-finished", step=self.cfg.total_steps,
                        device_peak_bytes=self._device_bytes("peak_bytes_in_use"),
                        step_compiles=obs_trace.STARTUP.step_compiles() or None,
                    )
        return state

    def _runtime_attrs(self) -> dict:
        """Where this run landed, for the ``train-started`` event: the device
        as JAX reports it, the mesh, which attention implementation the step
        resolves to, the bytes the freshly-initialised state holds on each
        local device — and what the model says of itself for this job's sizes
        (``run_description``: ``docs/observability.md`` has the counters by
        the module that owns each).  The control plane (and ``chip_smoke.py``)
        stays off JAX and learns the device from this."""
        from ..platform import device_report

        return {
            **device_report(),
            "mesh": {k: v for k, v in self.mesh.shape.items() if v > 1},
            "attention_impl": self.attention_impl,
            "device_state_bytes": self._device_bytes("bytes_in_use"),
            **self.model_cfg.run_description(
                seq_len=self.cfg.seq_len,
                tokens_per_microbatch=(
                    self.cfg.batch_size // self.cfg.grad_accum_steps
                    * self.cfg.seq_len),
                attention_impl=self.attention_impl, mesh=self.mesh,
                adapters=self._state_shapes.trainable),
        }

    def _device_bytes(self, stat: str) -> list[int] | None:
        """``memory_stats()[stat]`` of every local device, or None where the
        backend keeps no such statistics (the CPU)."""
        stats = [d.memory_stats() for d in self.mesh.local_devices]
        if not all(s and stat in s for s in stats):
            return None
        return [int(s[stat]) for s in stats]
