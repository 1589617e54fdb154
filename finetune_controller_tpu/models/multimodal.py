"""LLaVA-style multimodal SFT model: ViT encoder → projector → Llama decoder.

BASELINE config #5 (LLaVA-1.5 multimodal SFT). Architecture follows the
public LLaVA recipe — a vision transformer encodes the image into patch
embeddings, a 2-layer MLP projects them into the LM's embedding space, and
the projected patch tokens are *prepended* to the text embeddings so the
decoder attends to the image as a prefix. TPU-first notes:

- the ViT is plain bidirectional attention over a static patch grid (no
  masking, no ragged shapes) — pure MXU work XLA fuses well;
- the combined sequence is static: ``n_patches + text_len`` every step, so
  one compiled program serves the whole run;
- loss positions: only text-token targets count; the caller's ``loss_mask``
  is extended with zeros over the image prefix inside the model wrapper.

The reference has no model code at all (SURVEY.md §2.2); multimodal here is
a first-class model family beside Llama/Mixtral.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import PP_DENSE_TEXT_ONLY, LlamaConfig, RMSNorm, _proj
from .lora import LoRAConfig


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 336
    patch_size: int = 14
    d_model: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    d_ff: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # --- CLIP-compatibility knobs (round-5: real LLaVA towers import from
    # HF checkpoints — hf_import.load_llava_params). Defaults keep the
    # native recipe; the llava preset flips them to CLIP ViT-L/14 semantics.
    #: prepend a learned class token (CLIP); LLaVA's feature selection drops
    #: it from the encoder OUTPUT, but it participates in attention
    cls_token: bool = False
    #: LayerNorm right after embeddings (CLIP's pre_layrnorm)
    pre_norm: bool = False
    #: patch conv bias (CLIP uses none)
    patch_bias: bool = True
    #: MLP activation: "gelu" (exact, HF nn.GELU) | "quick_gelu"
    #: (x·sigmoid(1.702x) — OpenAI CLIP)
    act: str = "gelu"
    #: which hidden state feeds the projector: 0 = all layers + final norm
    #: (native); negative = CLIP hidden_states index (LLaVA-1.5 uses -2 —
    #: stop before the last layer, skip the post norm)
    feature_layer: int = 0
    #: LayerNorm epsilon (CLIP uses 1e-5; flax's default is 1e-6)
    ln_eps: float = 1e-5

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def replace(self, **kw) -> "ViTConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class LlavaConfig:
    vision: ViTConfig = dataclasses.field(default_factory=ViTConfig)
    text: LlamaConfig = dataclasses.field(default_factory=LlamaConfig)
    projector_hidden: int = 4096

    # trainer duck-type surface (mirrors LlamaConfig)
    @property
    def vocab_size(self) -> int:
        return self.text.vocab_size

    @property
    def lora(self) -> LoRAConfig:
        return self.text.lora

    # what a trainer asks of a model: the decoder's answers
    @property
    def sown(self) -> tuple[str, ...]:
        return self.text.sown

    @property
    def sown_in_eval(self) -> tuple[str, ...]:
        return self.text.sown_in_eval

    def sown_readings(self, collections: dict) -> tuple[Any, dict]:
        return self.text.sown_readings(collections)

    keeps_dtype = staticmethod(LlamaConfig.keeps_dtype)

    def refuse_mesh(self, mesh_shape: dict) -> None:
        if mesh_shape.get("pp", 1) > 1:
            raise ValueError(PP_DENSE_TEXT_ONLY)
        self.text.refuse_mesh(mesh_shape)

    def run_description(self, *, adapters: Any = None, **run) -> dict:
        """The decoder's, its adapters aside (the trainable tree holds the
        projector beside them)."""
        return self.text.run_description(**run)

    @property
    def attention_impl(self) -> str:
        return self.text.attention_impl

    @property
    def image_size(self) -> int:
        return self.vision.image_size

    @property
    def max_seq_len(self) -> int:
        """Decoder position budget — the image prefix (``n_patches``) and the
        text share it."""
        return self.text.max_seq_len

    def replace(self, **kw) -> "LlavaConfig":
        # route llama-level overrides (lora=...) into the text config
        text_keys = {f.name for f in dataclasses.fields(LlamaConfig)}
        text_kw = {k: v for k, v in kw.items() if k in text_keys}
        top_kw = {k: v for k, v in kw.items() if k not in text_keys}
        cfg = self
        if text_kw:
            cfg = dataclasses.replace(cfg, text=cfg.text.replace(**text_kw))
        if top_kw:
            cfg = dataclasses.replace(cfg, **top_kw)
        return cfg

    def param_count(self) -> int:
        v = self.vision
        vit = v.n_layers * (4 * v.d_model * v.d_model + 2 * v.d_model * v.d_ff)
        proj = v.d_model * self.projector_hidden + self.projector_hidden * self.text.d_model
        return vit + proj + self.text.param_count()


def _vit_act(cfg: ViTConfig, h: jax.Array) -> jax.Array:
    if cfg.act == "quick_gelu":
        return h * jax.nn.sigmoid(1.702 * h)
    if cfg.act == "gelu":
        return nn.gelu(h, approximate=False)
    raise ValueError(f"unknown ViT activation {cfg.act!r}")


class ViTBlock(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln1")(x)
        h = nn.MultiHeadDotProductAttention(
            num_heads=cfg.n_heads, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="attn",
        )(h, h)
        x = x + h
        h = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln2")(x)
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="fc1")(h)
        h = _vit_act(cfg, h)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="fc2")(h)
        return x + h


class ViTEncoder(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, pixels: jax.Array) -> jax.Array:
        """pixels (B, H, W, 3) → (B, n_patches, d_model).

        With ``cls_token`` the class token rides through attention and is
        dropped from the OUTPUT (LLaVA's "default" feature selection);
        ``feature_layer=-k`` stops k-1 layers early and skips the post norm
        (LLaVA-1.5 takes CLIP's hidden_states[-2])."""
        cfg = self.cfg
        x = nn.Conv(
            cfg.d_model,
            kernel_size=(cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            use_bias=cfg.patch_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="patch_embed",
        )(pixels.astype(cfg.dtype))
        b = x.shape[0]
        x = x.reshape(b, -1, cfg.d_model)
        n_tokens = cfg.n_patches
        if cfg.cls_token:
            cls = self.param(
                "cls", nn.initializers.normal(stddev=0.02),
                (1, 1, cfg.d_model), cfg.param_dtype,
            )
            x = jnp.concatenate(
                [jnp.broadcast_to(cls.astype(cfg.dtype), (b, 1, cfg.d_model)), x],
                axis=1,
            )
            n_tokens += 1
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, n_tokens, cfg.d_model),
            cfg.param_dtype,
        )
        x = x + pos.astype(cfg.dtype)
        if cfg.pre_norm:
            x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="pre_norm")(x)
        n_run = (
            cfg.n_layers if cfg.feature_layer == 0
            else cfg.n_layers + cfg.feature_layer + 1
        )
        if not 0 < n_run <= cfg.n_layers:
            raise ValueError(
                f"feature_layer {cfg.feature_layer} out of range for "
                f"{cfg.n_layers} layers"
            )
        for i in range(n_run):
            x = ViTBlock(cfg, name=f"block_{i}")(x)
        if cfg.feature_layer == 0:
            x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="final_norm")(x)
        if cfg.cls_token:
            x = x[:, 1:]  # feature selection drops CLS
        return x


class LlavaForCausalLM(nn.Module):
    """Image-prefix causal LM. Call with (tokens, pixels).

    KV-cached decode (round 5): ``decode=True`` with pixels fills the cache
    over the combined ``[image; text]`` sequence; subsequent single-token
    calls pass ``pixels=None`` and ABSOLUTE ``positions`` (offset by
    ``n_patches`` — the caller owns the position arithmetic, as in
    ``models/generate.py::cached_generate``)."""

    cfg: LlavaConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,               # (B, S)
        pixels: jax.Array | None = None,  # (B, H, W, 3)
        segment_ids: jax.Array | None = None,
        deterministic: bool = True,
        decode: bool = False,
        positions: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        tcfg = cfg.text
        b, s = tokens.shape

        embed = nn.Embed(
            tcfg.vocab_size, tcfg.d_model,
            dtype=tcfg.dtype, param_dtype=tcfg.param_dtype, name="embed_tokens",
        )
        text_emb = embed(tokens)                         # (B, S, d)

        n_img = 0
        if pixels is not None:
            patches = ViTEncoder(cfg.vision, name="vision_tower")(pixels)
            # 2-layer MLP projector (LLaVA-1.5 recipe)
            h = nn.Dense(cfg.projector_hidden, dtype=tcfg.dtype,
                         param_dtype=tcfg.param_dtype, name="projector_fc1")(patches)
            # exact GELU — HF's multi_modal_projector uses nn.GELU (erf
            # form), and the imported projector must reproduce it
            h = nn.gelu(h, approximate=False)
            img_emb = nn.Dense(tcfg.d_model, dtype=tcfg.dtype,
                               param_dtype=tcfg.param_dtype, name="projector_fc2")(h)
            n_img = img_emb.shape[1]
            x = jnp.concatenate([img_emb, text_emb], axis=1)
        else:
            x = text_emb

        total = n_img + s
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(total), (b, total))
        if segment_ids is not None and n_img:
            # image prefix joins the first text segment so text can attend to it
            first = segment_ids[:, :1]
            segment_ids = jnp.concatenate(
                [jnp.broadcast_to(first, (b, n_img)), segment_ids], axis=1
            )

        # reuse the Llama decoder stack over the combined sequence
        from .llama import Block, _ScanBlock, remat_policy_fn

        policy = remat_policy_fn(tcfg.remat_policy)
        if tcfg.scan_layers:
            block_cls = _ScanBlock
            if tcfg.remat and policy is not None:
                block_cls = nn.remat(
                    _ScanBlock, prevent_cse=False, static_argnums=(4, 5),
                    policy=policy,
                )
            stack = nn.scan(
                block_cls,
                variable_axes={"params": 0, "lora": 0, "moe_aux": 0,
                               "moe_stats": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast, nn.broadcast),
                length=tcfg.n_layers,
            )(tcfg, name="blocks")
            x, _ = stack(x, positions, segment_ids, deterministic, decode)
        else:
            block_cls = (
                nn.remat(Block, prevent_cse=False, static_argnums=(4, 5),
                         policy=policy)
                if tcfg.remat and policy is not None
                else Block
            )
            for i in range(tcfg.n_layers):
                x = block_cls(tcfg, name=f"layer_{i}")(
                    x, positions, segment_ids, deterministic, decode
                )

        x = RMSNorm(tcfg.rms_eps, tcfg.dtype, tcfg.param_dtype, tcfg.norm_offset, name="final_norm")(x)
        x = x[:, n_img:]                                 # logits for text positions only
        logits = _proj(tcfg.replace(lora=LoRAConfig()), "lm_head", tcfg.vocab_size)(x)
        return logits.astype(tcfg.logits_dtype or jnp.float32)

    def init_variables(self, rng: jax.Array, batch: int = 1, seq: int = 8):
        tokens = jnp.zeros((batch, seq), jnp.int32)
        size = self.cfg.vision.image_size
        pixels = jnp.zeros((batch, size, size, 3), jnp.float32)
        return self.init({"params": rng}, tokens, pixels)


MM_PRESETS: dict[str, LlavaConfig] = {
    "llava-1.5-7b": LlavaConfig(
        # CLIP ViT-L/14 @ 336px with LLaVA-1.5 semantics: class token, CLIP
        # pre-norm, quick-gelu, bias-free patch conv, penultimate-layer
        # features — the exact tower llava-hf/llava-1.5-7b-hf ships, so
        # hf_import.load_llava_params maps it 1:1
        vision=ViTConfig(cls_token=True, pre_norm=True, patch_bias=False,
                         act="quick_gelu", feature_layer=-2),
        text=LlamaConfig(
            vocab_size=32064, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, d_ff=11008, max_seq_len=4096, attention_impl="auto",
        ),
        projector_hidden=4096,
    ),
    "tiny-mm-test": LlavaConfig(
        vision=ViTConfig(image_size=16, patch_size=8, d_model=32, n_layers=2,
                         n_heads=2, d_ff=64),
        text=LlamaConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128,
        ),
        projector_hidden=64,
    ),
    # CLIP-semantics tiny model: the import/e2e test shape — structurally a
    # miniature llava-1.5-7b (class token, pre-norm, quick-gelu,
    # penultimate-layer features), loadable from a tiny HF LLaVA checkpoint
    "tiny-mm-clip-test": LlavaConfig(
        vision=ViTConfig(image_size=16, patch_size=8, d_model=32, n_layers=3,
                         n_heads=2, d_ff=64, cls_token=True, pre_norm=True,
                         patch_bias=False, act="quick_gelu", feature_layer=-2),
        text=LlamaConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, rms_eps=1e-6,
        ),
        projector_hidden=64,
    ),
}
