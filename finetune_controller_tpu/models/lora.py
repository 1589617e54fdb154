"""LoRA adapters as a first-class parameter collection.

The frozen base weights live in the ``"params"`` collection; adapters live in
a separate ``"lora"`` collection.  The trainer differentiates only w.r.t. the
trainable collection, so no gradients or optimizer state are ever materialised
for the frozen base — the property that makes 8B LoRA fit a v5e chip.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

DEFAULT_TARGETS = (
    "q_proj",
    "k_proj",
    "v_proj",
    "o_proj",
    "gate_proj",
    "up_proj",
    "down_proj",
)

#: the projections of a latent-attention block (``models/llama.py``
#: MLAttention) beside the dense / shared-expert MLP's: what PEFT recipes for
#: that family target (routed experts and the router stay frozen)
MLA_TARGETS = (
    "q_a_proj",
    "q_b_proj",
    "kv_a_proj_with_mqa",
    "kv_b_proj",
    "o_proj",
    "gate_proj",
    "up_proj",
    "down_proj",
)

#: the projections of a hybrid block (``models/llama.py`` Block with
#: ``models/ssm.py`` Mamba2Mixer beside attention): attention's four, the
#: mixer's two and the MLP's three
HYBRID_TARGETS = (
    "q_proj",
    "k_proj",
    "v_proj",
    "o_proj",
    "in_proj",
    "out_proj",
    "gate_proj",
    "up_proj",
    "down_proj",
)


#: the projections of a pattern model's three layer kinds (``models/llama.py``
#: ``LlamaConfig.layer_pattern``): attention's four, the mixer's two, the
#: expert layer's two latent projections and its shared expert's two (routed
#: experts and the router stay frozen)
PATTERN_TARGETS = (
    "q_proj",
    "k_proj",
    "v_proj",
    "o_proj",
    "in_proj",
    "out_proj",
    "fc1_latent_proj",
    "fc2_latent_proj",
    "up_proj",
    "down_proj",
)


#: the projections of a block of a sparse / lightning pattern
#: (``models/llama.py::SparseAttention``, ``models/ssm.py::LightningMixer``):
#: either mixer's four and its output gate, and the MLP's three
GATED_MIXER_TARGETS = (
    "q_proj",
    "k_proj",
    "v_proj",
    "o_proj",
    "o_gate",
    "gate_proj",
    "up_proj",
    "down_proj",
)


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 0            # 0 disables LoRA (full fine-tune)
    alpha: float = 16.0
    dropout: float = 0.0
    targets: Sequence[str] = DEFAULT_TARGETS

    def enabled_for(self, name: str) -> bool:
        return self.rank > 0 and name in self.targets


def _contract_rows(u: jax.Array, v: jax.Array) -> jax.Array:
    """``uᵀ @ v`` over every leading axis: ``[..., m], [..., n] -> [m, n]`` in
    float32 (an adapter's gradient is rounded once, to its float32 leaf)."""
    lead = tuple(range(u.ndim - 1))
    return jax.lax.dot_general(
        u, v, ((lead, lead), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def joined_product(x, kernel, a, b, scale: float):
    """``x @ kernel + scale * (x @ a) @ b`` with the adapter's rank-``r``
    products inside the base product's contraction, forward and backward, so
    that no pass over ``[T, in]`` or ``[T, out]`` exists for the adapter's sake
    but the three a rank-``r`` result cannot do without (``x`` for ``x @ a``
    and for ``da``, ``dy`` for ``dh`` and ``db``):

        h  = scale * (x @ a)                  lora_delta
        y  = [x | h] @ [[kernel], [b]]        base_matmul

        dh = scale * (dy @ bᵀ)                lora_delta
        db = hᵀ @ dy,  da = xᵀ @ dh           lora_delta
        dx = [dy | dh] @ [kernel | a]ᵀ        base_matmul

    The identity is exact; the sum of base and delta is accumulated in
    float32 inside one product and rounded once (apart, each is rounded and
    so is their sum).  The chip's compiler fuses both concatenations into the
    product's operands and writes neither; it does read ``[x | h]`` in the
    layout it gives the ``r``-wide ``h`` (a row's tokens minor-most), so an
    ``x`` that sits in another, and a scanned stack's carry, are written
    once more in that one (PERF.md sections 5 and 6, PR 37).  Which
    projections take this form: ``joins_base_product``.  ``x`` is
    ``[..., in]`` and ``kernel`` ``[in, out]`` in the compute dtype; ``a``
    ``[in, r]`` and ``b`` ``[r, out]`` are the adapter's own (float32) leaves
    and get float32 gradients.
    """
    return _joined_fwd(x, kernel, a, b, scale)[0]


def _joined_fwd(x, kernel, a, b, scale):
    dtype = x.dtype
    with jax.named_scope("lora_delta"):
        h = jnp.matmul(x, a.astype(dtype), preferred_element_type=jnp.float32)
        h = (h * scale).astype(dtype)
    with jax.named_scope("base_matmul"):
        y = jnp.concatenate([x, h], axis=-1) @ jnp.concatenate(
            [kernel, b.astype(dtype)], axis=0)
    return y, (x, kernel, a, b, h)


def _joined_bwd(scale, res, dy):
    x, kernel, a, b, h = res
    dtype = x.dtype
    with jax.named_scope("lora_delta"):
        dh = jnp.matmul(dy, b.astype(dtype).T, preferred_element_type=jnp.float32)
        dh = (dh * scale).astype(dtype)
        db = _contract_rows(h, dy).astype(b.dtype)
        da = _contract_rows(x, dh).astype(a.dtype)
    with jax.named_scope("base_matmul"):
        dx = jnp.concatenate([dy, dh], axis=-1) @ jnp.concatenate(
            [kernel, a.astype(dtype)], axis=1).T
        # a frozen base's is dead code the compiler drops
        dkernel = _contract_rows(x, dy).astype(kernel.dtype)
    return dx, dkernel, da, db


joined_product.defvjp(_joined_fwd, _joined_bwd)


def mesh_splits() -> tuple[int, bool]:
    """What the mesh the caller is traced under (``parallel.ring.ring_mesh``,
    as ``ops/pallas::bare_mosaic_call_ok`` reads it) does to a projection: over
    how many devices its rows lie (the batch on ``dp`` and ``fsdp``, a row's
    tokens on ``sp``: a traced shape is the global one), and whether it splits
    the axes the joined operands are concatenated along (a kernel's ``in`` and
    ``out`` lie on ``fsdp`` and ``tp``, ``parallel/sharding.py``).  Inside a
    ``shard_map`` body the shapes are a device's own: ``(1, False)``."""
    from ..parallel.ring import get_ring_mesh

    mesh = get_ring_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return 1, False
    dp, fsdp, sp, tp = (
        mesh.shape.get(axis, 1) for axis in ("dp", "fsdp", "sp", "tp"))
    return dp * fsdp * sp, fsdp > 1 or tp > 1


#: the compiler under which the cutoff of ``joins_base_product`` was read on
#: the chip (PERF.md section 6, PR 37).  Which side of it wins is this
#: compiler's fusions, not arithmetic: read both sides again under another
#: (``tests/test_lora_product.py`` says when this one has gone).
CUTOFF_READ_UNDER = {"jax": "0.9.0", "libtpu": "0.0.34"}


def joins_base_product(rows: int, in_features: int, rank: int, *,
                       sharded: bool = False) -> bool:
    """Whether an adapted projection that puts ``rows`` tokens through one
    device takes the joined form (``joined_product``):
    ``rows >= 1.75 * (in + r)``.

    A cutoff read on the v5e, not derived (PERF.md section 6, PR 37).  Joined,
    the delta's passes over ``[rows, out]`` and ``[rows, in]`` go and the
    product's contraction is ``r`` deeper, which this compiler charges the
    matrix unit as some 500 rows of depth (a product of depth 4,096 + 16 runs
    11 % slower than one of 4,096): both are in proportion to rows x out, the
    two nearly cancel for every shape, and which side wins is what XLA fuses
    around the product.  What the chip read, by rows / (in + r): joined
    FASTER at 3.98, 2.49 and 1.99 (Mistral's six projections of 4,096
    columns on 16,384, 10,240 and 8,192 rows: the step -1.5, -2.9, -3.6 %),
    at 1.99 again (the hybrid cell's ``out_proj``, -0.39 %; the expert
    cell's ``o_proj``, no difference) and, never alone, from 2.66 up (the
    expert and 16k cells' latent projections); SLOWER at 1.14 (Mistral's
    ``down_proj``, in every pairing) and at 1.60 (the hybrid cell's
    ``in_proj``, ``gate_proj`` and ``up_proj``, 5,120 columns on 8,192 rows:
    +0.9 % between them).  The cutoff is halfway between 1.60 and 1.99.
    Every decode and prefill shape of the serve engine (32 to 2,048 rows on
    kernels thousands of rows deep) is far below it, and a projection whose
    joined axes the mesh in scope splits (``sharded``) stays apart whatever
    its shapes.
    """
    return (rank > 0 and not sharded
            and 4 * rows >= 7 * (in_features + rank))


def joined_projections(adapters: Any, tokens: int, *, dropout: bool) -> dict:
    """How many of the adapted projections in ``adapters`` (a tree of the
    adapter leaves' shapes) carry their adapter inside the base product, of
    how many, and which keep the two apart: :func:`joins_base_product` asked
    as the step's trace asks it — at each adapter's own widths, ``tokens`` a
    microbatch, under the mesh in scope.  ``dropout`` between the adapter's
    factors keeps every one apart."""
    devices, sharded = mesh_splits()
    joined = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(adapters):
        *module, name = (k.key for k in path)
        if name == "lora_a":    # [(layers,) in, r]
            joined["/".join(module)] = not dropout and joins_base_product(
                tokens // devices, leaf.shape[-2], leaf.shape[-1],
                sharded=sharded)
    return {
        "joined": sum(joined.values()),
        "of": len(joined),
        "apart": sorted(p for p, j in joined.items() if not j),
    }


class LoRADense(nn.Module):
    """Dense layer with an optional low-rank adapter branch.

    ``y = x @ W  +  (alpha / r) * (x @ A) @ B`` with ``A: (in, r)`` normal-init
    and ``B: (r, out)`` zero-init, so the adapter starts as identity.  Where
    ``joins_base_product`` says so the sum is ONE product with a hand-written
    backward rule (``joined_product``); training dropout on the adapter's
    input, ``lora_rank == 0`` and the ``tenants`` branch keep the code below.
    """

    features: int
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_dropout: float = 0.0
    use_bias: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    #: store the frozen base kernel as blockwise int4 (QLoRA — models/quant.py)
    quantize_base: bool = False
    quant_block: int = 64
    #: multi-tenant serving (docs/serving.md §Multi-tenant adapters): when
    #: > 0, a ``"tenants"`` collection holds ``tenant_slots`` stacked
    #: per-tenant adapters — ``lora_a (N, in, r)``, ``lora_b (N, r, out)``,
    #: ``scale (N,)`` — and each batch row applies the adapter named by its
    #: entry in the per-row ``adapter_ids`` vector via a gathered batched
    #: einsum.  Slot 0 is the base model (all-zero stack, scale 0 — the
    #: delta is an exact 0.0).  Tenants whose trained rank is below
    #: ``tenant_rank`` are zero-padded: the extra rank columns/rows
    #: contribute exactly nothing, so the padded math is bit-equal to the
    #: unpadded adapter.
    tenant_slots: int = 0
    tenant_rank: int = 0

    @nn.compact
    def __call__(self, x, deterministic: bool = True, adapter_ids=None):
        in_features = x.shape[-1]
        if self.quantize_base:
            from .quant import quantized_param

            kernel = quantized_param(
                self, "kernel", (in_features, self.features),
                self.kernel_init, self.quant_block, self.dtype,
            )
        else:
            kernel = self.param(
                "kernel", self.kernel_init, (in_features, self.features),
                self.param_dtype,
            ).astype(self.dtype)
        # leaves are made in the order they always were (an initialiser's
        # random stream counts the calls before it): kernel, bias, adapter
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype
        ) if self.use_bias else None
        a = b = None
        if self.lora_rank > 0:
            a = self.variable(
                "lora",
                "lora_a",
                nn.initializers.normal(stddev=0.02),
                self.make_rng("params") if self.is_initializing() else None,
                (in_features, self.lora_rank),
                self.param_dtype,
            ).value
            b = self.variable(
                "lora",
                "lora_b",
                lambda _rng, shape, dt: jnp.zeros(shape, dt),
                None,
                (self.lora_rank, self.features),
                self.param_dtype,
            ).value
            scale = self.lora_alpha / self.lora_rank
        dropout = self.lora_dropout > 0.0 and not deterministic
        joined = False
        if a is not None and not dropout:
            devices, sharded = mesh_splits()
            joined = joins_base_product(
                x.size // in_features // devices, in_features, self.lora_rank,
                sharded=sharded)
        if joined:
            y = joined_product(x, kernel, a, b, scale)
        else:
            with jax.named_scope("base_matmul"):
                y = x @ kernel
        if bias is not None:
            y = y + bias.astype(self.dtype)
        if a is not None and not joined:
            h = x
            if dropout:
                h = nn.Dropout(rate=self.lora_dropout, deterministic=False)(h)
            with jax.named_scope("lora_delta"):
                y = y + (h @ a.astype(self.dtype)) @ b.astype(self.dtype) * scale
        if self.tenant_slots > 0 and adapter_ids is not None:
            # per-row tenant adapters: y_b += scale[t_b] * (x_b @ A[t_b]) @
            # B[t_b] with t = adapter_ids — the unmerged-LoRA multiplexing
            # math (same eval order as the single-adapter branch above, so a
            # one-tenant registry reproduces it exactly up to the gather)
            n, r = self.tenant_slots, max(1, self.tenant_rank)
            ta = self.variable(
                "tenants", "lora_a",
                lambda *_: jnp.zeros((n, in_features, r), self.param_dtype),
                None,
            ).value
            tb = self.variable(
                "tenants", "lora_b",
                lambda *_: jnp.zeros((n, r, self.features), self.param_dtype),
                None,
            ).value
            ts = self.variable(
                "tenants", "scale",
                lambda *_: jnp.zeros((n,), self.param_dtype),
                None,
            ).value
            ids = adapter_ids.astype(jnp.int32)
            ha = jnp.einsum("bsi,bir->bsr", x, ta[ids].astype(self.dtype))
            delta = jnp.einsum("bsr,bro->bso", ha, tb[ids].astype(self.dtype))
            y = y + delta * ts[ids].astype(self.dtype)[:, None, None]
        return y
