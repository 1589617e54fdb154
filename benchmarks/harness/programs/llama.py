"""The Llama-family program: ``LlamaConfig`` from a configuration file's
PUBLISHED keys (never from a preset a later PR may edit) plus the file's
``run`` section.  Found by name (``"program": "llama"``, the default)."""

from __future__ import annotations

from typing import Any


def model_config(conf: dict, **overrides):
    """The program's model config from the published keys plus the file's
    ``run`` section (how this benchmark runs the model)."""
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.lora import LoRAConfig

    run = conf["run"]
    if conf.get("sliding_window") and conf.get("use_sliding_window", True):
        raise ValueError("a windowed configuration needs a windowed program")
    kw: dict[str, Any] = dict(
        vocab_size=conf["vocab_size"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]),
        max_seq_len=int(run["max_seq_len"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        attention_qkv_bias=bool(run.get("attention_qkv_bias", False)),
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.float32,
        logits_dtype=jnp.dtype(run["logits_dtype"]),
        attention_impl=run["attention_impl"],
        remat_policy=run["remat_policy"],
        quantize_base=bool(run["quantize_base"]),
        quant_block=int(run.get("quant_block", 64)),
        lora=LoRAConfig(rank=int(run["lora_rank"]),
                        alpha=float(run["lora_alpha"]),
                        targets=tuple(run["lora_targets"])),
    )
    if conf["hidden_size"] // conf["num_attention_heads"] != conf.get(
            "head_dim", conf["hidden_size"] // conf["num_attention_heads"]):
        kw["head_dim_override"] = conf["head_dim"]
    kw.update(overrides)
    return LlamaConfig(**kw)
