"""Export trained artifacts in HuggingFace-consumable formats.

The other half of ``hf_import.py``: after a fine-tune, users need artifacts
their serving stack understands — either a **PEFT adapter** directory
(``adapter_model.safetensors`` + ``adapter_config.json``, loadable with
``peft.PeftModel``) or a **merged full checkpoint** (``model.safetensors`` +
``config.json``, loadable with ``transformers``). The reference delegates all
artifact formats to user containers (SURVEY.md §2.2); here the trainer owns
them, so promotion publishes something deployable.

Both paths are round-trip tested against ``peft``/``transformers`` in
``tests/test_hf_export.py``.
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Any

import numpy as np

from .llama import LlamaConfig
from .quant import dequantize_int4

logger = logging.getLogger(__name__)

#: our projection name → HF module path fragment
_HF_MODULE = {
    "q_proj": "self_attn.q_proj",
    "k_proj": "self_attn.k_proj",
    "v_proj": "self_attn.v_proj",
    "o_proj": "self_attn.o_proj",
    "gate_proj": "mlp.gate_proj",
    "up_proj": "mlp.up_proj",
    "down_proj": "mlp.down_proj",
}


def _save_safetensors(path: Path, tensors: dict[str, np.ndarray]) -> None:
    from safetensors.numpy import save_file

    save_file({k: np.ascontiguousarray(v) for k, v in tensors.items()}, str(path))


def _stacked_lora_modules(lora_tree: dict) -> dict[str, dict[str, np.ndarray]]:
    """Flatten the scanned lora tree → {proj_name: {lora_a, lora_b}} with the
    leading layer axis intact."""
    blocks = lora_tree["blocks"]["block"]
    out: dict[str, dict[str, np.ndarray]] = {}
    for group in blocks.values():            # attn / mlp
        for proj, leaves in group.items():
            out[proj] = {k: np.asarray(v) for k, v in leaves.items()}
    return out


def export_lora_adapter(
    cfg: LlamaConfig,
    lora_tree: dict,
    out_dir: Path | str,
    *,
    base_model_name: str = "",
    hf_prefix: str = "base_model.model.model.layers",
) -> Path:
    """Write a PEFT-format LoRA adapter directory.

    PEFT stores ``lora_A.weight (r, in)`` / ``lora_B.weight (out, r)`` per
    target module with scaling ``alpha / r`` — ours are flax ``(in, r)`` /
    ``(r, out)`` kernels with the same scaling, so the export is a transpose
    per tensor (verified numerically against ``peft`` in the tests).
    ``hf_prefix`` names the base model's layer path — multimodal adapters
    target the decoder nested under ``language_model`` in HF's LLaVA.
    """
    out_dir = Path(out_dir).expanduser()
    out_dir.mkdir(parents=True, exist_ok=True)
    modules = _stacked_lora_modules(lora_tree)
    tensors: dict[str, np.ndarray] = {}
    for proj, leaves in modules.items():
        a, b = leaves["lora_a"], leaves["lora_b"]     # (L, in, r), (L, r, out)
        for i in range(a.shape[0]):
            prefix = f"{hf_prefix}.{i}.{_HF_MODULE[proj]}"
            tensors[f"{prefix}.lora_A.weight"] = a[i].T.astype(np.float32)
            tensors[f"{prefix}.lora_B.weight"] = b[i].T.astype(np.float32)
    _save_safetensors(out_dir / "adapter_model.safetensors", tensors)

    adapter_config = {
        "peft_type": "LORA",
        "task_type": "CAUSAL_LM",
        "base_model_name_or_path": base_model_name,
        "r": cfg.lora.rank,
        "lora_alpha": cfg.lora.alpha,
        "lora_dropout": cfg.lora.dropout,
        "target_modules": sorted(modules),
        "bias": "none",
        "fan_in_fan_out": False,
        "inference_mode": True,
    }
    (out_dir / "adapter_config.json").write_text(json.dumps(adapter_config, indent=2))
    logger.info("wrote PEFT adapter (%d tensors) -> %s", len(tensors), out_dir)
    return out_dir


def export_mm_projector(projector: dict, out_dir: Path | str) -> Path:
    """Write the trained LLaVA projector beside the adapter, in HF's
    ``multi_modal_projector`` naming — the piece the LLaVA recipe trains
    outside the PEFT adapter (upstream llava ships it as
    ``non_lora_trainables``; ours is a safetensors file a deploy script maps
    straight onto ``LlavaForConditionalGeneration``)."""
    out_dir = Path(out_dir).expanduser()
    out_dir.mkdir(parents=True, exist_ok=True)
    tensors = {
        "multi_modal_projector.linear_1.weight": np.asarray(
            projector["projector_fc1"]["kernel"], np.float32).T,
        "multi_modal_projector.linear_1.bias": np.asarray(
            projector["projector_fc1"]["bias"], np.float32),
        "multi_modal_projector.linear_2.weight": np.asarray(
            projector["projector_fc2"]["kernel"], np.float32).T,
        "multi_modal_projector.linear_2.bias": np.asarray(
            projector["projector_fc2"]["bias"], np.float32),
    }
    path = out_dir / "projector.safetensors"
    _save_safetensors(path, tensors)
    logger.info("wrote multimodal projector -> %s", path)
    return path


def _base_kernel(leaves: dict[str, np.ndarray], layer: int, cfg: LlamaConfig) -> np.ndarray:
    """(in, out) f32 base kernel for one layer, dequantizing QLoRA storage."""
    if "kernel" in leaves:
        return np.asarray(leaves["kernel"][layer], np.float32)
    deq = dequantize_int4(
        leaves["kernel_packed"][layer], leaves["kernel_scales"][layer],
        dtype=np.float32,
    )
    return np.asarray(deq, np.float32)


def _expert_stack(moe: dict, name: str, layer: int) -> np.ndarray:
    """(E, in, out) f32 expert kernels of projection ``name`` for one layer,
    dequantizing int4 expert storage (the MoE-QLoRA path —
    ``models/moe.py``)."""
    leaves = moe["experts"][name]
    if "kernel" in leaves:
        return np.asarray(leaves["kernel"][layer], np.float32)
    packed = leaves["kernel_packed"][layer]
    scales = leaves["kernel_scales"][layer]
    return np.stack([
        np.asarray(dequantize_int4(packed[e], scales[e], dtype=np.float32))
        for e in range(packed.shape[0])
    ])


def _hf_layout(cfg: LlamaConfig) -> tuple[str, str]:
    """(architecture, model_type) for the config's semantics; raises on
    combinations no HF architecture encodes."""
    if (cfg.attention_kind != "gqa" or cfg.first_k_dense
            or cfg.n_shared_experts or cfg.moe_scoring != "softmax"
            or cfg.ssm_d_inner or cfg.layer_pattern):
        raise NotImplementedError(
            "latent attention, leading dense layers, shared-expert sigmoid "
            "routing, a state-space mixer beside attention and a pattern of "
            "layer kinds have no transformers export yet (ROADMAP.md B); "
            "export the PEFT adapter instead"
        )
    gemma_markers = (cfg.norm_offset, cfg.embed_scale, cfg.mlp_act != "silu")
    if any(gemma_markers):
        # Gemma semantics: HF stores the SAME offset-form norm weights and
        # applies the same sqrt(d) embed scaling/GeGLU from config, so the
        # tensors export unchanged — only the config names the architecture
        if not all([cfg.norm_offset == 1.0, cfg.embed_scale,
                    cfg.mlp_act == "gelu", cfg.tie_embeddings]):
            raise NotImplementedError(
                "partial Gemma semantics (norm_offset/embed_scale/mlp_act "
                "mix) matches no transformers architecture; export the PEFT "
                "adapter instead"
            )
        if cfg.n_experts:
            raise NotImplementedError(
                "Gemma-semantics MoE matches no transformers architecture"
            )
        arch, model_type = "GemmaForCausalLM", "gemma"
    elif cfg.n_experts:
        arch, model_type = "MixtralForCausalLM", "mixtral"
    elif cfg.attention_qkv_bias:
        arch, model_type = "Qwen2ForCausalLM", "qwen2"
    else:
        arch, model_type = "LlamaForCausalLM", "llama"
    if cfg.rope_scaling_factor and model_type != "llama":
        # only the Llama-3.x presets carry rope_scaling today; another
        # layout with it set would get a config.json whose llama3
        # rope_scaling block transformers rejects — refuse BEFORE any
        # tensor file is written
        raise NotImplementedError(
            f"rope_scaling export is only supported for the llama layout, "
            f"not {model_type!r}"
        )
    return arch, model_type


def export_merged_checkpoint(
    cfg: LlamaConfig,
    variables: dict[str, Any],
    out_dir: Path | str,
) -> Path:
    """Write a full HF checkpoint with LoRA deltas merged into the base
    (``W_eff = W + (alpha/r)·A·B``), loadable by ``transformers`` — the
    importer's inverse, covering every shipped text family: Llama/Qwen-2
    dense, Gemma (offset norms/GeGLU/embed scaling ride the config), and
    Mixtral MoE (stacked experts unstacked to per-expert ``w1/w2/w3``,
    int4-quantized experts dequantized)."""
    arch, model_type = _hf_layout(cfg)  # raises before any file is written
    out_dir = Path(out_dir).expanduser()
    out_dir.mkdir(parents=True, exist_ok=True)
    params = variables["params"]
    lora = variables.get("lora", {})
    lora_blocks = lora.get("blocks", {}).get("block", {}) if lora else {}
    blocks = params["blocks"]["block"]
    scale = cfg.lora.alpha / cfg.lora.rank if cfg.lora.rank else 0.0

    tensors: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(
            params["embed_tokens"]["embedding"], np.float32
        ),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"], np.float32),
    }
    if not cfg.tie_embeddings:
        tensors["lm_head.weight"] = np.asarray(
            params["lm_head"]["kernel"], np.float32
        ).T

    for i in range(cfg.n_layers):
        prefix = f"model.layers.{i}"
        tensors[f"{prefix}.input_layernorm.weight"] = np.asarray(
            blocks["attn_norm"]["scale"][i], np.float32
        )
        tensors[f"{prefix}.post_attention_layernorm.weight"] = np.asarray(
            blocks["mlp_norm"]["scale"][i], np.float32
        )
        groups = ("attn",) if cfg.n_experts else ("attn", "mlp")
        for group_name in groups:
            for proj, leaves in blocks[group_name].items():
                kernel = _base_kernel(leaves, i, cfg)           # (in, out)
                ladder = lora_blocks.get(group_name, {}).get(proj)
                if ladder is not None:
                    a = np.asarray(ladder["lora_a"][i], np.float32)
                    b = np.asarray(ladder["lora_b"][i], np.float32)
                    kernel = kernel + scale * (a @ b)
                tensors[f"{prefix}.{_HF_MODULE[proj]}.weight"] = kernel.T
                if "bias" in leaves:  # Qwen-2 q/k/v biases (frozen, no LoRA)
                    tensors[f"{prefix}.{_HF_MODULE[proj]}.bias"] = np.asarray(
                        leaves["bias"][i], np.float32
                    )
        if cfg.n_experts:
            moe = blocks["moe"]
            mp = f"{prefix}.block_sparse_moe"
            tensors[f"{mp}.gate.weight"] = np.asarray(
                moe["router"]["kernel"][i], np.float32
            ).T
            # stacked (E, in, out) → per-expert HF (out, in); the importer's
            # w1=gate / w2=down / w3=up mapping, inverted
            for name, hf_w in (("gate_proj", "w1"), ("down_proj", "w2"),
                               ("up_proj", "w3")):
                stack = _expert_stack(moe, name, i)
                for e in range(stack.shape[0]):
                    tensors[f"{mp}.experts.{e}.{hf_w}.weight"] = stack[e].T

    _save_safetensors(out_dir / "model.safetensors", tensors)
    hf_config = {
        "architectures": [arch],
        "model_type": model_type,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        # explicit so a decoupled head_dim (head_dim_override) reconstructs
        # the same attention shapes in transformers
        "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_seq_len,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": cfg.attention_qkv_bias,
        "mlp_bias": False,
        "torch_dtype": "float32",
    }
    if model_type == "gemma":
        # transformers' Gemma applies GeGLU (tanh approximation), the (1+w)
        # norm form, and sqrt(d) embed scaling from the architecture itself —
        # both config keys are set for pre/post-4.39 transformers
        hf_config["hidden_act"] = "gelu_pytorch_tanh"
        hf_config["hidden_activation"] = "gelu_pytorch_tanh"
    if model_type == "mixtral":
        hf_config["num_local_experts"] = cfg.n_experts
        hf_config["num_experts_per_tok"] = cfg.moe_top_k
        hf_config["router_aux_loss_coef"] = cfg.router_aux_weight
    if cfg.rope_scaling_factor:
        # non-llama layouts were refused in _hf_layout, before any write
        hf_config["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": cfg.rope_scaling_factor,
            "low_freq_factor": cfg.rope_scaling_low_freq_factor,
            "high_freq_factor": cfg.rope_scaling_high_freq_factor,
            "original_max_position_embeddings": cfg.rope_scaling_original_max_len,
        }
    (out_dir / "config.json").write_text(json.dumps(hf_config, indent=2))
    logger.info("wrote merged HF checkpoint (%d tensors) -> %s", len(tensors), out_dir)
    return out_dir
