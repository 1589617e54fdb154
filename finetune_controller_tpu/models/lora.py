"""LoRA adapters as a first-class parameter collection.

The frozen base weights live in the ``"params"`` collection; adapters live in
a separate ``"lora"`` collection.  The trainer differentiates only w.r.t. the
trainable collection, so no gradients or optimizer state are ever materialised
for the frozen base — the property that makes 8B LoRA fit a v5e chip.
"""

from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 0            # 0 disables LoRA (full fine-tune)
    alpha: float = 16.0
    dropout: float = 0.0
    targets: Sequence[str] = DEFAULT_TARGETS

    def enabled_for(self, name: str) -> bool:
        return self.rank > 0 and name in self.targets


class LoRADense(nn.Module):
    """Dense layer with an optional low-rank adapter branch.

    ``y = x @ W  +  (alpha / r) * (x @ A) @ B`` with ``A: (in, r)`` normal-init
    and ``B: (r, out)`` zero-init, so the adapter starts as identity.
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
        with jax.named_scope("base_matmul"):
            y = x @ kernel
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype
            )
            y = y + bias.astype(self.dtype)
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
            h = x
            if self.lora_dropout > 0.0 and not deterministic:
                h = nn.Dropout(rate=self.lora_dropout, deterministic=False)(h)
            scale = self.lora_alpha / self.lora_rank
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
