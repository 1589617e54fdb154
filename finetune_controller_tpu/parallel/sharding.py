"""Path-pattern → PartitionSpec rules for parameter and activation sharding.

Megatron+FSDP layout for the Llama family:

* column-parallel kernels (qkv, gate/up proj): ``P(fsdp, tp)`` — output
  features split over TP, input features sharded over FSDP so the weight
  all-gather rides ICI right before the matmul.
* row-parallel kernels (o proj, down proj): ``P(tp, fsdp)``.
* embeddings / lm head: vocab over TP, model dim over FSDP.
* norms / biases / scalars: replicated.

Rules are ordered regexes over the ``/``-joined param path; first match wins.
"""

from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import AxisNames as Ax


class ShardingRuleError(ValueError):
    """A partition rule resolved to a spec the mesh cannot apply to a leaf:
    a spec axis the mesh does not define, or a mesh-axis product that does
    not divide the leaf dimension it shards.  Raised upfront by
    :func:`sharding_for_tree` with the offending path and spec — before the
    bad rule can surface as a deep XLA partitioner error at compile time."""


class PartitionRules:
    def __init__(self, rules: list[tuple[str, P]]):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def entries(self) -> list[tuple[str, P]]:
        """The ordered ``(pattern, spec)`` table — introspection surface for
        the sharding-conformance lint rules (``analysis/rules_sharding.py``)."""
        return [(pat.pattern, spec) for pat, spec in self._rules]

    def match_index(self, path: str) -> int | None:
        """Index of the first rule whose pattern matches ``path`` (the rule
        :meth:`spec_for` would select), or None."""
        for i, (pat, _spec) in enumerate(self._rules):
            if pat.search(path):
                return i
        return None

    def fingerprint(self) -> str:
        """Stable digest of the ordered rule table.

        Stamped into every checkpoint manifest (``train/elastic.py``): a
        restore onto a model whose rule table differs — reordered rules, a
        changed spec, a new carve-out — would silently mis-shard the state,
        so elastic restore refuses a checkpoint whose fingerprint does not
        match the live table.  Patterns AND specs both feed the digest;
        order matters (first match wins at lookup time).
        """
        import hashlib

        parts = [
            f"{pat.pattern}\x00{tuple(spec)!r}" for pat, spec in self._rules
        ]
        digest = hashlib.sha256("\x01".join(parts).encode()).hexdigest()
        return f"sha256:{digest}"

    def spec_for(self, path: str, value: Any = None) -> P:
        for pat, spec in self._rules:
            if pat.search(path):
                ndim = getattr(value, "ndim", None)
                if value is None or ndim is None or len(spec) == ndim:
                    return spec
                if len(spec) == ndim - 1 and "blocks" in path:
                    # Layer-stacked (nn.scan) params carry a leading layer
                    # axis — the pipeline axis. With pp=1 this is a no-op;
                    # with pp>1 each stage holds its contiguous layer shard.
                    return P(Ax.PIPE, *spec)
                if len(spec) > ndim:
                    # Rank-mismatch safety: replicate rather than mis-shard.
                    return P()
                return spec
        return P()

    def tree_specs(self, tree: Any) -> Any:
        """Map a pytree of arrays (or ShapeDtypeStructs) to PartitionSpecs."""
        return jax.tree_util.tree_map_with_path(
            lambda kp, v: self.spec_for(key_path_str(kp), v), tree
        )


def key_path_str(kp) -> str:
    """``/``-joined param path for a jax key path — the string the rule
    patterns match against."""
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# Llama-family parameter rules.  Kernel shapes as produced by
# finetune_controller_tpu.models.llama (Dense kernels are (in, out)).
LLAMA_RULES = PartitionRules(
    [
        # token embedding: (vocab, d_model)
        (r"embed_tokens/embedding", P(Ax.TENSOR, Ax.FSDP)),
        # lm head kernel: (d_model, vocab)
        (r"lm_head/kernel", P(Ax.FSDP, Ax.TENSOR)),
        # MoE experts (models/moe.py): stacked (n_experts, in, out) under
        # experts/<projection>/, experts over EP so expert matmuls are local
        # and token exchange is all-to-all.  These precede the dense
        # projection rules, whose patterns the paths also contain.  Int4
        # scales first (same tiny-block-dim reasoning as the dense
        # kernel_scales carve-outs): (E, in/block, out) keeps the block dim
        # whole and shards only experts + the feature dim
        (r"experts/(gate_proj|up_proj)/kernel_scales", P(Ax.EXPERT, None, Ax.TENSOR)),
        (r"experts/down_proj/kernel_scales", P(Ax.EXPERT, None, Ax.FSDP)),
        (r"experts/(gate_proj|up_proj)/kernel", P(Ax.EXPERT, Ax.FSDP, Ax.TENSOR)),
        (r"experts/down_proj/kernel", P(Ax.EXPERT, Ax.TENSOR, Ax.FSDP)),
        (r"router/kernel", P(Ax.FSDP, None)),
        # experts in a latent (models/moe.py): the projection into it feeds
        # the rows that are dispatched to the experts whole, so its output
        # stays whole over TP, as the latents of attention do; the projection
        # back out takes the combined sum whole and splits what it writes
        (r"fc1_latent_proj/kernel", P(Ax.FSDP, None)),
        (r"fc2_latent_proj/kernel", P(None, Ax.FSDP)),
        # the selection bias: one number an expert, whole everywhere
        (r"router/bias", P()),
        # a window layer's sink (models/llama.py Attention): one float32 logit
        # a query head, whole everywhere — the flash call's shard_map splits
        # it with the heads (ops/attention.py)
        (r"attn/sink/bias", P()),
        # QLoRA int4 scales: (in/block, out) — the block dim is tiny, keep it
        # whole and shard only the feature dim (must precede the kernel rules,
        # which would otherwise also match "kernel_scales")
        (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/kernel_scales", P(None, Ax.TENSOR)),
        (r"(o_proj|down_proj|out_proj)/kernel_scales", P(None, Ax.FSDP)),
        # attention projections (kernel and int4-packed kernel share layout)
        (r"(q_proj|k_proj|v_proj)/kernel", P(Ax.FSDP, Ax.TENSOR)),
        (r"o_proj/kernel", P(Ax.TENSOR, Ax.FSDP)),
        # the output gate of block-sparse and lightning attention
        # (models/llama.py gated_output): a column a channel of the mixer's
        # output, split with the heads as q_proj's
        (r"o_gate/kernel", P(Ax.FSDP, Ax.TENSOR)),
        (r"o_gate/lora_a", P(Ax.FSDP, None)),
        (r"o_gate/lora_b", P(None, Ax.TENSOR)),
        # the state-space mixer (models/ssm.py): its input projection splits
        # by output feature and its output projection by input feature, as
        # attention's do (heads over TP); the convolution, the per-head
        # A_log / D / dt_bias and the gated norm's scale are 30 k numbers a
        # layer, whole everywhere DELIBERATELY
        (r"in_proj/kernel", P(Ax.FSDP, Ax.TENSOR)),
        (r"out_proj/kernel", P(Ax.TENSOR, Ax.FSDP)),
        (r"mamba/(conv1d|A_log|D|dt_bias|norm)/", P()),
        # latent attention (models/llama.py MLAttention): the down-projections
        # into the latents feed a norm over the whole latent, so their output
        # stays whole over TP; the up-projections out of the latents split by
        # head like q/k/v (o_proj is the rule above)
        (r"(q_a_proj|kv_a_proj_with_mqa)/kernel", P(Ax.FSDP, None)),
        (r"(q_b_proj|kv_b_proj)/kernel", P(Ax.FSDP, Ax.TENSOR)),
        # an indexer's leaves (models/llama.py Indexer), one or a stack of
        # them: whole everywhere DELIBERATELY — 9 M numbers an indexer beside
        # 165 M of attention projections, and the scores they give are taken
        # over all keys on every member
        (r"indexer/", P()),
        # MLP
        (r"(gate_proj|up_proj)/kernel", P(Ax.FSDP, Ax.TENSOR)),
        (r"down_proj/kernel", P(Ax.TENSOR, Ax.FSDP)),
        # multimodal projector (models/multimodal.py): fc1 (d_vision, hidden)
        # column-parallel, fc2 (hidden, d_model) row-parallel
        (r"projector_fc1/kernel", P(Ax.FSDP, Ax.TENSOR)),
        (r"projector_fc2/kernel", P(Ax.TENSOR, Ax.FSDP)),
        # ViT tower: replicated DELIBERATELY — the encoder is small next to
        # the decoder and frozen in the LLaVA recipe.  The explicit rule
        # (rather than catch-all fallthrough) keeps the shard-rule-coverage
        # lint's weight-fallthrough check meaningful: a kernel reaching the
        # bare catch-all below means someone ADDED a weight family without
        # deciding its sharding
        (r"vision_tower/", P()),
        # LoRA adapters: A (in, r) sharded like the frozen kernel's input dim;
        # B (r, out) over the output dim.  Rank r is tiny — keep it replicated.
        (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/lora_a", P(Ax.FSDP, None)),
        (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/lora_b", P(None, Ax.TENSOR)),
        (r"(q_a_proj|kv_a_proj_with_mqa|q_b_proj|kv_b_proj)/lora_a", P(Ax.FSDP, None)),
        (r"(q_a_proj|kv_a_proj_with_mqa)/lora_b", P()),
        (r"(q_b_proj|kv_b_proj)/lora_b", P(None, Ax.TENSOR)),
        (r"fc1_latent_proj/lora_a", P(Ax.FSDP, None)),
        (r"fc1_latent_proj/lora_b", P()),
        (r"fc2_latent_proj/lora_a", P()),
        (r"fc2_latent_proj/lora_b", P(None, Ax.FSDP)),
        (r"(o_proj|down_proj|out_proj)/lora_a", P(Ax.TENSOR, None)),
        (r"(o_proj|down_proj|out_proj)/lora_b", P(None, Ax.FSDP)),
        # norms, scales, biases — replicated
        (r".*", P()),
    ]
)


def validate_spec(path: str, shape: tuple, spec: P, mesh: Mesh) -> None:
    """Prove ``spec`` is applicable to a ``shape``-shaped leaf on ``mesh``:
    every named axis exists, and the product of mesh-axis sizes sharding a
    dimension divides that dimension.  Raises :class:`ShardingRuleError`
    naming the path/spec/dim — the typed, immediate form of what would
    otherwise surface as a deep XLA partitioner error at compile time."""
    mesh_shape = dict(mesh.shape)
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        factor = 1
        for ax in axes:
            if ax not in mesh_shape:
                raise ShardingRuleError(
                    f"partition rule for {path!r} resolved to spec {spec} "
                    f"naming mesh axis {ax!r}, but the mesh only defines "
                    f"axes {tuple(mesh_shape)} — fix the rule table or the "
                    "mesh builder (parallel/mesh.py)"
                )
            factor *= mesh_shape[ax]
        if dim >= len(shape) or (factor > 1 and shape[dim] % factor):
            dim_size = shape[dim] if dim < len(shape) else "<missing>"
            raise ShardingRuleError(
                f"partition rule for {path!r} resolved to spec {spec}, but "
                f"dim {dim} of shape {tuple(shape)} (size {dim_size}) is not "
                f"divisible by the {factor}-way mesh sharding over "
                f"axes {tuple(axes)}"
            )


def sharding_for_tree(tree: Any, mesh: Mesh, rules: PartitionRules) -> Any:
    specs = rules.tree_specs(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    for (kp, leaf), spec in zip(
        jax.tree_util.tree_leaves_with_path(tree), spec_leaves
    ):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            validate_spec(key_path_str(kp), tuple(shape), spec, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh, seq_sharded: bool = True) -> NamedSharding:
    """Sharding for (batch, seq[, ...]) token arrays: batch over dp+fsdp, seq
    over sp (ring/context parallelism) when requested."""
    seq_axis = Ax.SEQ if seq_sharded else None
    return NamedSharding(mesh, P(Ax.BATCH_AXES, seq_axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
