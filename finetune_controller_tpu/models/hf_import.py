"""Import pretrained HuggingFace Llama-family checkpoints into our param tree.

The reference never loads weights — training runs in user containers that
bring their own (SURVEY.md §2.2). A TPU-native fine-tuning framework has to
own this step: this module maps a local HF checkpoint directory
(``*.safetensors`` shards or ``pytorch_model.bin``) onto the flax parameter
tree the trainer shards, covering the dense Llama family (TinyLlama, Llama-3,
Mistral) and Mixtral's MoE experts.

Layout notes (why the transposes/stacks below are correct):

* HF ``nn.Linear`` stores ``(out_features, in_features)``; flax ``Dense``
  kernels are ``(in, out)`` → transpose every projection.
* our decoder runs under ``nn.scan`` — per-layer trees are stacked on a
  leading layer axis (the same axis pp shards), so layer ``i``'s tensors land
  at ``stacked[i]``.
* RoPE conventions match (both rotate half-vectors with the same frequency
  table), so no head permutation is needed — verified numerically against
  ``transformers``' reference implementation in ``tests/test_hf_import.py``.

No network egress happens here: the checkpoint directory must already be on
disk (in-cluster: staged like a dataset through the object store).
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Any, Iterator

import jax
import numpy as np

from .llama import LlamaConfig

logger = logging.getLogger(__name__)


def _iter_checkpoint_tensors(ckpt_dir: Path) -> Iterator[tuple[str, np.ndarray]]:
    """Yield (hf_name, array) from safetensors shards or a torch .bin file."""
    st_files = sorted(ckpt_dir.glob("*.safetensors"))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(str(f), framework="np") as reader:
                for name in reader.keys():
                    yield name, reader.get_tensor(name)
        return
    bin_files = sorted(ckpt_dir.glob("pytorch_model*.bin"))
    if not bin_files:
        raise FileNotFoundError(
            f"no *.safetensors or pytorch_model*.bin under {ckpt_dir}"
        )
    import torch

    for f in bin_files:
        state = torch.load(str(f), map_location="cpu", weights_only=True)
        for name, tensor in state.items():
            yield name, tensor.float().numpy()


def _strip(name: str) -> str:
    return name.removeprefix("model.")


def load_llama_params(
    ckpt_dir: Path | str,
    cfg: LlamaConfig,
    *,
    dtype: Any = None,
) -> dict[str, Any]:
    """Build the model's ``params`` collection from an HF checkpoint dir.

    Returns a tree matching ``LlamaForCausalLM`` with ``scan_layers=True``
    (blocks stacked on the leading layer axis). Raises on missing/unexpected
    tensors so a architecture/config mismatch fails loudly at load, not as
    silent garbage training.
    """
    ckpt_dir = Path(ckpt_dir).expanduser()
    pairs = ((_strip(n), a) for n, a in _iter_checkpoint_tensors(ckpt_dir))
    params = _map_llama_tensors(pairs, cfg, dtype or cfg.param_dtype)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    logger.info("loaded %d tensors (%.1fM params) from %s",
                len(jax.tree.leaves(params)), n_params / 1e6, ckpt_dir)
    return params


def _map_llama_tensors(
    pairs, cfg: LlamaConfig, dtype: Any
) -> dict[str, Any]:
    """Map stripped ``(hf_name, array)`` pairs onto the Llama param tree
    (shared by the text-only loader and the LLaVA language-model half)."""
    if cfg.layer_pattern:
        raise NotImplementedError(
            "a pattern of layer kinds has no checkpoint import yet: its "
            "leaves are not one block's stacked over the layers "
            "(ROADMAP.md B10)")
    L = cfg.n_layers

    # staging area: per-layer dicts to stack once everything is read
    layers: list[dict[str, np.ndarray]] = [dict() for _ in range(L)]
    top: dict[str, np.ndarray] = {}
    unexpected: list[str] = []

    for key, arr in pairs:
        if "rotary_emb.inv_freq" in key:
            # non-persistent RoPE buffer serialized by transformers < 4.32
            # (Llama-2-era .bin checkpoints); recomputed from config here
            continue
        if key == "embed_tokens.weight":
            top["embedding"] = arr
        elif key == "norm.weight":
            top["final_norm"] = arr
        elif key == "lm_head.weight":
            top["lm_head"] = arr.T
        elif key.startswith("layers."):
            _, idx_s, rest = key.split(".", 2)
            idx = int(idx_s)
            if idx >= L:
                raise ValueError(
                    f"checkpoint layer {idx} out of range for n_layers={L}"
                )
            layers[idx][rest] = arr
        else:
            unexpected.append(key)
    if unexpected:
        raise ValueError(f"unexpected checkpoint tensors: {unexpected[:5]}")

    def proj(rest: dict, hf: str) -> np.ndarray:
        return rest.pop(hf).T  # (out, in) -> (in, out)

    def layer_tree(rest: dict[str, np.ndarray], idx: int) -> dict[str, Any]:
        tree: dict[str, Any] = {
            "attn_norm": {"scale": rest.pop("input_layernorm.weight")},
            "mlp_norm": {"scale": rest.pop("post_attention_layernorm.weight")},
            "attn": {
                "q_proj": {"kernel": proj(rest, "self_attn.q_proj.weight")},
                "k_proj": {"kernel": proj(rest, "self_attn.k_proj.weight")},
                "v_proj": {"kernel": proj(rest, "self_attn.v_proj.weight")},
                "o_proj": {"kernel": proj(rest, "self_attn.o_proj.weight")},
            },
        }
        if cfg.attention_qkv_bias:
            # Qwen-2 family: q/k/v carry biases (o_proj does not)
            for p in ("q_proj", "k_proj", "v_proj"):
                tree["attn"][p]["bias"] = rest.pop(f"self_attn.{p}.bias")
        if cfg.n_experts:
            gate = []
            up = []
            down = []
            for e in range(cfg.n_experts):
                gate.append(proj(rest, f"block_sparse_moe.experts.{e}.w1.weight"))
                down.append(proj(rest, f"block_sparse_moe.experts.{e}.w2.weight"))
                up.append(proj(rest, f"block_sparse_moe.experts.{e}.w3.weight"))
            tree["moe"] = {
                "experts": {
                    "gate_proj": {"kernel": np.stack(gate)},
                    "up_proj": {"kernel": np.stack(up)},
                    "down_proj": {"kernel": np.stack(down)},
                },
                "router": {
                    "kernel": proj(rest, "block_sparse_moe.gate.weight")},
            }
        else:
            tree["mlp"] = {
                "gate_proj": {"kernel": proj(rest, "mlp.gate_proj.weight")},
                "up_proj": {"kernel": proj(rest, "mlp.up_proj.weight")},
                "down_proj": {"kernel": proj(rest, "mlp.down_proj.weight")},
            }
        if rest:
            raise ValueError(f"layer {idx}: unmapped tensors {sorted(rest)[:5]}")
        return tree

    missing = [i for i, rest in enumerate(layers) if not rest]
    if missing:
        raise ValueError(f"checkpoint has no tensors for layers {missing[:5]}")
    trees = [layer_tree(rest, i) for i, rest in enumerate(layers)]
    stacked = jax.tree.map(lambda *xs: np.stack(xs).astype(dtype), *trees)

    if "embedding" not in top or "final_norm" not in top:
        raise ValueError("checkpoint missing embed_tokens/norm weights")
    params: dict[str, Any] = {
        "embed_tokens": {"embedding": top["embedding"].astype(dtype)},
        "blocks": {"block": stacked},
        "final_norm": {"scale": top["final_norm"].astype(dtype)},
    }
    if cfg.tie_embeddings:
        if "lm_head" in top:
            logger.info("tie_embeddings=True: ignoring separate lm_head weight")
    else:
        if "lm_head" not in top:
            raise ValueError(
                "checkpoint has no lm_head.weight but cfg.tie_embeddings=False"
            )
        params["lm_head"] = {"kernel": top["lm_head"].astype(dtype)}
    return params


# ---------------------------------------------------------------------------
# LLaVA: CLIP vision tower + projector + Llama language model (round 5)
# ---------------------------------------------------------------------------


def _map_vision_tensors(vt: dict[str, np.ndarray], vcfg, dtype) -> dict[str, Any]:
    """Map CLIP vision-model tensors (``vision_tower.vision_model.`` stripped)
    onto our :class:`~.multimodal.ViTEncoder` tree.

    Layout notes: HF conv weight ``(out, in, h, w)`` → flax ``(h, w, in,
    out)``; q/k/v/out ``(d, d)`` matrices reshape onto flax
    ``MultiHeadDotProductAttention``'s ``(d, H, hd)`` / ``(H, hd, d)``
    kernels. With ``feature_layer=-k`` the final ``k-1`` encoder layers and
    the post norm exist in the checkpoint but are never run (LLaVA-1.5 takes
    hidden_states[-2]) — they are skipped, not errors."""
    d, H = vcfg.d_model, vcfg.n_heads
    hd = d // H
    tree: dict[str, Any] = {}

    def pop(key: str) -> np.ndarray:
        try:
            return vt.pop(key)
        except KeyError:
            raise ValueError(
                f"vision tower missing tensor {key!r} — config/checkpoint "
                "mismatch"
            ) from None

    tree["patch_embed"] = {
        "kernel": pop("embeddings.patch_embedding.weight").transpose(2, 3, 1, 0)
    }
    if vcfg.patch_bias:
        tree["patch_embed"]["bias"] = pop("embeddings.patch_embedding.bias")
    tree["pos_embed"] = pop("embeddings.position_embedding.weight")[None]
    if vcfg.cls_token:
        tree["cls"] = pop("embeddings.class_embedding").reshape(1, 1, d)
    if vcfg.pre_norm:
        # (the "pre_layrnorm" typo is transformers' own attribute name)
        tree["pre_norm"] = {
            "scale": pop("pre_layrnorm.weight"),
            "bias": pop("pre_layrnorm.bias"),
        }
    n_run = (
        vcfg.n_layers if vcfg.feature_layer == 0
        else vcfg.n_layers + vcfg.feature_layer + 1
    )
    for i in range(n_run):
        p = f"encoder.layers.{i}."

        def qkv(nm: str) -> dict[str, np.ndarray]:
            return {
                "kernel": pop(f"{p}self_attn.{nm}_proj.weight").T.reshape(d, H, hd),
                "bias": pop(f"{p}self_attn.{nm}_proj.bias").reshape(H, hd),
            }

        tree[f"block_{i}"] = {
            "ln1": {"scale": pop(f"{p}layer_norm1.weight"),
                    "bias": pop(f"{p}layer_norm1.bias")},
            "attn": {
                "query": qkv("q"), "key": qkv("k"), "value": qkv("v"),
                "out": {
                    "kernel": pop(f"{p}self_attn.out_proj.weight").T.reshape(H, hd, d),
                    "bias": pop(f"{p}self_attn.out_proj.bias"),
                },
            },
            "ln2": {"scale": pop(f"{p}layer_norm2.weight"),
                    "bias": pop(f"{p}layer_norm2.bias")},
            "fc1": {"kernel": pop(f"{p}mlp.fc1.weight").T,
                    "bias": pop(f"{p}mlp.fc1.bias")},
            "fc2": {"kernel": pop(f"{p}mlp.fc2.weight").T,
                    "bias": pop(f"{p}mlp.fc2.bias")},
        }
    if vcfg.feature_layer == 0:
        tree["final_norm"] = {
            "scale": pop("post_layernorm.weight"),
            "bias": pop("post_layernorm.bias"),
        }
    # tensors the selected feature layer never touches
    skippable = tuple(
        f"encoder.layers.{i}." for i in range(n_run, vcfg.n_layers)
    ) + (("post_layernorm.",) if vcfg.feature_layer != 0 else ())
    leftover = [k for k in vt if not k.startswith(skippable)]
    if leftover:
        raise ValueError(f"unmapped vision tensors: {sorted(leftover)[:5]}")
    return jax.tree.map(lambda x: np.asarray(x, dtype), tree)


def load_llava_params(
    ckpt_dir: Path | str,
    cfg,  # LlavaConfig
    *,
    dtype: Any = None,
) -> dict[str, Any]:
    """Build ``LlavaForCausalLM``'s ``params`` collection from an HF LLaVA
    checkpoint dir (``LlavaForConditionalGeneration`` layout:
    ``vision_tower.vision_model.*`` + ``multi_modal_projector.*`` +
    ``language_model.*``). Numerically parity-tested against transformers in
    ``tests/test_hf_import.py``."""
    ckpt_dir = Path(ckpt_dir).expanduser()
    dtype = dtype or cfg.text.param_dtype

    text_pairs: list[tuple[str, np.ndarray]] = []
    vision: dict[str, np.ndarray] = {}
    proj: dict[str, np.ndarray] = {}
    unexpected: list[str] = []
    for name, arr in _iter_checkpoint_tensors(ckpt_dir):
        # transformers >= 4.52 nests the text model under model.*
        name = name.removeprefix("model.")
        if name.startswith("language_model."):
            text_pairs.append((_strip(name.removeprefix("language_model.")), arr))
        elif name.startswith("vision_tower.vision_model."):
            vision[name.removeprefix("vision_tower.vision_model.")] = arr
        elif name.startswith("multi_modal_projector."):
            proj[name.removeprefix("multi_modal_projector.")] = arr
        else:
            unexpected.append(name)
    if unexpected:
        raise ValueError(f"unexpected checkpoint tensors: {unexpected[:5]}")

    params = _map_llama_tensors(iter(text_pairs), cfg.text, dtype)
    params["vision_tower"] = _map_vision_tensors(vision, cfg.vision, dtype)
    try:
        params["projector_fc1"] = {
            "kernel": np.asarray(proj.pop("linear_1.weight").T, dtype),
            "bias": np.asarray(proj.pop("linear_1.bias"), dtype),
        }
        params["projector_fc2"] = {
            "kernel": np.asarray(proj.pop("linear_2.weight").T, dtype),
            "bias": np.asarray(proj.pop("linear_2.bias"), dtype),
        }
    except KeyError as e:
        raise ValueError(f"projector missing tensor {e}") from None
    if proj:
        raise ValueError(f"unmapped projector tensors: {sorted(proj)[:5]}")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    logger.info("loaded LLaVA checkpoint (%.1fM params) from %s",
                n_params / 1e6, ckpt_dir)
    return params
