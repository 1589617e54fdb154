"""The latent-attention expert program: ``LlamaConfig`` — the ONE decoder of
``models/llama.py`` with its block's parts chosen — from a configuration
file's PUBLISHED keys (latent attention with q/k heads of ``qk_nope_head_dim
+ qk_rope_head_dim`` beside v heads of ``v_head_dim``, ``first_k_dense_replace``
leading dense layers, then dropless sigmoid top-k routing over
``n_routed_experts`` beside ``n_shared_experts`` shared ones) plus the file's
``run`` section (of its own: ``"selection_bias": "seeded" | "zero"``, whether
the router's selection bias is a leaf the benchmark draws or is held at
zero).  Found by name (``"program": "mla_moe"``)."""

from __future__ import annotations

from typing import Any


def model_config(conf: dict, **overrides):
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.lora import LoRAConfig

    run = conf["run"]
    if conf.get("rope_scaling"):
        raise ValueError("a scaled-RoPE configuration needs a program that scales")
    if conf.get("n_group", 1) != 1 or conf.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing needs a program that limits groups")
    if conf.get("moe_layer_freq", 1) != 1:
        raise ValueError("expert layers at another frequency than every layer")
    if not conf["norm_topk_prob"]:
        raise ValueError("un-normalised top-k weights need a program that keeps them")
    if conf["scoring_func"] not in ("sigmoid", "softmax"):
        raise ValueError(f"no such scoring function: {conf['scoring_func']!r}")
    if run.get("selection_bias", "seeded") not in ("seeded", "zero"):
        raise ValueError(f"selection_bias is seeded or zero, not "
                         f"{run['selection_bias']!r}")
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
        attention_kind="mla",
        q_lora_rank=conf["q_lora_rank"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_interleave=bool(conf.get("rope_interleave", False)),
        first_k_dense=conf["first_k_dense_replace"],
        n_experts=conf["n_routed_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        moe_scoring=conf["scoring_func"],
        moe_dispatch="dropless",
        # noaux_tc: balanced by the frozen selection bias, no auxiliary loss.
        # A run that holds that bias at zero ("selection_bias": "zero": the
        # balanced load a trained bias gives, which no seeded draw does)
        # builds the layer without the leaf
        moe_select_bias=(conf["topk_method"] == "noaux_tc"
                         and run.get("selection_bias", "seeded") == "seeded"),
        router_aux_weight=0.0,
        moe_routed_scale=float(conf["routed_scaling_factor"]),
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
    kw.update(overrides)
    return LlamaConfig(**kw)
