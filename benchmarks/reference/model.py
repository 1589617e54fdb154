"""Plain float32 forward pass of a Llama-family decoder.

Written from the published descriptions (Mistral-7B and Qwen2 model cards and
``config.json``): token embedding; per layer RMSNorm -> grouped-query causal
attention with rotary embedding (rotate-half convention) -> residual ->
RMSNorm -> SwiGLU -> residual; final RMSNorm; untied output head.  Optional
q/k/v biases (Qwen2).  Every projection may carry a LoRA branch
``(alpha / r) * (x @ A) @ B``.  No kernel, no cache, no scan: one Python call
per layer, so a 7 B model never exists in float32 — each layer's weights are
regenerated from the seed (``harness/weights.py``), dequantised or up-cast,
used and dropped.

Departures from the published models, all stated: weights are random from a
seed; the frozen base is stored as the configuration file says (blockwise
int4 with bf16 scales, or bf16) and the reference computes on the exact
float32 value of what is stored.

``q`` is the control's hook: a function applied to BOTH operands of every
matrix product.  The reference passes the identity and runs under
``jax.default_matmul_precision("highest")``; the lower-precision control
rounds operands, and the cotangents flowing back through them, to scaled
float8 (``to_fp8``), the step below the bf16 the configurations state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..harness import weights


def identity(x):
    return x


def _round_scaled(x, dtype, top: float):
    """Round to ``dtype`` and back, scaled per tensor so the largest
    magnitude sits at the format's top — the per-tensor scaling an fp8
    matmul path uses."""
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def to_fp8(x):
    """An fp8 matmul path's operand: float8 e4m3 (3 mantissa bits) on the
    way forward, and the cotangent in float8 e5m2 (2 mantissa bits, wider
    range) on the way back, each scaled per tensor.  (Differentiating the
    bare cast would cast the UNSCALED cotangent to float8, which underflows
    to zero: a control that reads nothing.)"""
    return _round_scaled(x, jnp.float8_e4m3fn, 448.0)


to_fp8.defvjp(lambda x: (to_fp8(x), None),
              lambda _, g: (_round_scaled(g, jnp.float8_e5m2, 57344.0),))


@dataclasses.dataclass(frozen=True)
class Arch:
    vocab_size: int
    hidden_size: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_eps: float
    qkv_bias: bool
    quantized: bool
    quant_block: int
    base_dtype: str          # how unquantised frozen leaves are stored
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple

    @classmethod
    def from_config(cls, conf: dict, n_layers: int | None = None) -> "Arch":
        run = conf["run"]
        heads = conf["num_attention_heads"]
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            n_layers=n_layers or conf["num_hidden_layers"], n_heads=heads,
            n_kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim", conf["hidden_size"] // heads),
            intermediate_size=conf["intermediate_size"],
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            qkv_bias=bool(run.get("attention_qkv_bias", False)),
            quantized=bool(run["quantize_base"]),
            quant_block=int(run.get("quant_block", 64)),
            base_dtype=run["frozen_dtype"],
            lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
        )

    def proj_shapes(self) -> dict[str, tuple[int, int]]:
        d, hd = self.hidden_size, self.head_dim
        f = self.intermediate_size
        return {
            "attn/q_proj": (d, self.n_heads * hd),
            "attn/k_proj": (d, self.n_kv_heads * hd),
            "attn/v_proj": (d, self.n_kv_heads * hd),
            "attn/o_proj": (self.n_heads * hd, d),
            "mlp/gate_proj": (d, f), "mlp/up_proj": (d, f),
            "mlp/down_proj": (f, d),
        }


def dequant_int4(packed, scales, block: int):
    """Blockwise symmetric int4 -> float32.  Byte ``i`` of a column holds
    input rows ``2i`` (low nibble) and ``2i+1`` (high nibble), each a 4-bit
    two's-complement integer; rows ``[b*block, (b+1)*block)`` share
    ``scales[b]``."""
    lo = (packed & 0x0F).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    lo = lo - 16 * (lo >= 8)
    hi = hi - 16 * (hi >= 8)
    half, out = packed.shape
    w = jnp.stack([lo, hi], axis=1).reshape(2 * half, out).astype(jnp.float32)
    s = jnp.repeat(scales.astype(jnp.float32), block, axis=0)
    return w * s


def layer_weights(arch: Arch, key, layer,
                  prefix: str = weights.STACKED) -> dict[str, Any]:
    """One layer's frozen weights in float32, regenerated from the seed:
    layer ``layer`` of the scanned stack, or with another ``prefix`` a layer
    that lies outside it under that name (``layer`` 0 then)."""
    base = jnp.dtype(arch.base_dtype)
    out: dict[str, Any] = {}
    for norm in ("attn_norm", "mlp_norm"):
        out[norm] = weights.layer_leaf(
            key, f"{prefix}/{norm}/scale", layer, (arch.hidden_size,), base
        ).astype(jnp.float32)
    for name, (i, o) in arch.proj_shapes().items():
        if arch.quantized:
            packed = weights.layer_leaf(
                key, f"{prefix}/{name}/kernel_packed", layer, (i // 2, o),
                jnp.uint8)
            scales = weights.layer_leaf(
                key, f"{prefix}/{name}/kernel_scales", layer,
                (i // arch.quant_block, o), jnp.bfloat16, arch.quant_block)
            out[name] = dequant_int4(packed, scales, arch.quant_block)
        else:
            out[name] = weights.layer_leaf(
                key, f"{prefix}/{name}/kernel", layer, (i, o), base
            ).astype(jnp.float32)
        if arch.qkv_bias and name.split("/")[1] in ("q_proj", "k_proj", "v_proj"):
            out[name + "/bias"] = weights.layer_leaf(
                key, f"{prefix}/{name}/bias", layer, (o,), base
            ).astype(jnp.float32)
    return out


def init_lora(arch: Arch, key) -> dict[str, Any]:
    """The seeded adapters, stacked over layers: ``name -> (L, ...)``."""
    out = {}
    for name, (i, o) in arch.proj_shapes().items():
        if name.split("/")[1] not in arch.lora_targets or not arch.lora_rank:
            continue
        for leaf_name, shape in (("lora_a", (i, arch.lora_rank)),
                                 ("lora_b", (arch.lora_rank, o))):
            full = f"blocks/{name}/{leaf_name}"
            out[full] = weights.leaf(
                key, full, (arch.n_layers,) + shape, jnp.float32, stacked=True)
    return out


def top_weights(arch: Arch, key) -> dict[str, Any]:
    base = jnp.dtype(arch.base_dtype)
    d, v = arch.hidden_size, arch.vocab_size
    return {
        "embedding": weights.leaf(key, "embed_tokens/embedding", (v, d), base,
                                  stacked=False),
        "final_norm": weights.leaf(key, "final_norm/scale", (d,), base,
                                   stacked=False).astype(jnp.float32),
        "lm_head": weights.leaf(key, "lm_head/kernel", (d, v), base,
                                stacked=False),
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: (B, S, H, D); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv       # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


#: the largest float32 score array (B, heads, S, S) the reference makes in
#: one piece: 2 rows x 32 heads at 2,048 fill it exactly, and one row at
#: 8,192 (8.6 GB whole, more under its ``vjp``) goes four heads at a time
SCORE_BYTES = 1 << 30


def heads_per_block(rows: int, heads: int, seq: int) -> int:
    """How many heads attend at once, from shapes alone: all of them where
    their scores fit ``SCORE_BYTES``, else the largest divisor of ``heads``
    that does."""
    fit = max(1, SCORE_BYTES // (rows * seq * seq * 4))
    return max(h for h in range(1, heads + 1) if heads % h == 0 and h <= fit)


def layer_forward(arch: Arch, w: dict, lora_l: dict, x, positions,
                  q: Callable = identity):
    """One decoder layer.  ``lora_l``: this layer's adapters by full name
    (``blocks/attn/q_proj/lora_a`` ...), absent = no branch."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        if name + "/bias" in w:
            y = y + w[name + "/bias"]
        a = lora_l.get(f"blocks/{name}/lora_a")
        if a is not None:
            b = lora_l[f"blocks/{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    bsz, s, _ = x.shape
    hd, nh, nkv = arch.head_dim, arch.n_heads, arch.n_kv_heads
    h = rms_norm(x, w["attn_norm"], arch.rms_eps)
    qh = rope(proj("attn/q_proj", h).reshape(bsz, s, nh, hd), positions,
              arch.rope_theta)
    kh = rope(proj("attn/k_proj", h).reshape(bsz, s, nkv, hd), positions,
              arch.rope_theta)
    vh = proj("attn/v_proj", h).reshape(bsz, s, nkv, hd)
    g = nh // nkv
    kh = jnp.repeat(kh, g, axis=2)
    vh = jnp.repeat(vh, g, axis=2)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]

    def attend(qb, kb, vb):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q(qb), q(kb)) * hd ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(vb))

    hb = heads_per_block(bsz, nh, s)
    if hb == nh:
        ctx = attend(qh, kh, vh)
    else:
        # heads are independent: one block of them at a time, its scores
        # recomputed on the way back, so no (B, H, S, S) array ever exists
        def split(t):
            return jnp.moveaxis(t.reshape(bsz, s, nh // hb, hb, hd), 2, 0)

        ctx = jax.lax.map(lambda b: jax.checkpoint(attend)(*b),
                          (split(qh), split(kh), split(vh)))
        ctx = jnp.moveaxis(ctx, 0, 2)
    ctx = ctx.reshape(bsz, s, nh * hd)
    x = x + proj("attn/o_proj", ctx)
    h = rms_norm(x, w["mlp_norm"], arch.rms_eps)
    act = jax.nn.silu(proj("mlp/gate_proj", h)) * proj("mlp/up_proj", h)
    return x + proj("mlp/down_proj", act)


def head_logits(arch: Arch, top: dict, x, q: Callable = identity):
    h = rms_norm(x, top["final_norm"], arch.rms_eps)
    return jnp.matmul(q(h), q(top["lm_head"].astype(jnp.float32)))


def _layer_lora(lora: dict, layer) -> dict:
    return {k: v[layer] for k, v in lora.items()}


def make_forward(arch: Arch, q: Callable = identity, precision="highest"):
    """``forward(key, lora, tokens, rows) -> logits`` of the listed positions
    (``rows``: (n,) indices into the sequence), for tokens (1, S)."""

    @jax.jit
    def embed(key, tokens):
        return top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)

    @jax.jit
    def layer(key, lora, l, x):
        with jax.default_matmul_precision(precision):
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            return layer_forward(arch, layer_weights(arch, key, l),
                                 _layer_lora(lora, l), x, pos, q)

    @jax.jit
    def head(key, x, rows):
        with jax.default_matmul_precision(precision):
            return head_logits(arch, top_weights(arch, key), x[:, rows], q)

    def forward(key, lora, tokens, rows):
        x = embed(key, tokens)
        for l in range(arch.n_layers):
            x = layer(key, lora, jnp.asarray(l, jnp.int32), x)
        return head(key, x, rows)

    return forward
