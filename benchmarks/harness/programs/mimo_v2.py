"""The window/full-attention expert program: ``LlamaConfig`` — the ONE decoder
of ``models/llama.py`` — from a configuration file's PUBLISHED keys for a model
whose layers are whole blocks that differ by their ATTENTION's kind.
``hybrid_layer_pattern`` places them, one number a layer: ``0`` full attention
(``num_key_value_heads`` key/value heads, RoPE base ``rope_theta``, every
earlier key), ``1`` window attention (``swa_num_key_value_heads``, base
``swa_rope_theta``, ``sliding_window`` keys, a learned sink a head where
``add_swa_attention_sink_bias``); both at q/k heads of ``head_dim`` (the first
``int(head_dim x partial_rotary_factor)`` columns rotated) beside v heads of
``v_head_dim`` scaled by ``attention_value_scale``.  ``moe_layer_freq`` places
the leading dense layers (``0``, a SwiGLU of ``intermediate_size``) before the
expert layers (``1``: sigmoid top-``num_experts_per_tok`` of the published
``n_routed_experts`` experts of ``moe_intermediate_size``, no shared one).  A
file that holds a share of the experts (``n_routed_experts`` in ``reduced``)
routes over the published count and computes its own: ``experts_held = (0,
n_routed_experts)``.  ``run.selection_bias`` (``"seeded"`` | ``"zero"``) as
``programs/mla_moe.py`` reads it.  Found by name (``"program": "mimo_v2"``).
Refuses what it does not compute."""

from __future__ import annotations

from typing import Any

from benchmarks.harness.programs.mla_dsa_moe import published_experts

#: a published key -> the one value this program computes
ONLY = {
    "model_type": "mimo_v2_flash", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_shared_experts": None,
    # a sink on the full kind needs a program that builds one there
    "add_full_attention_sink_bias": False,
}
#: pairs of published keys that say one thing twice
SAME = (("sliding_window", "sliding_window_size"), ("swa_head_dim", "head_dim"),
        ("swa_v_head_dim", "v_head_dim"),
        ("swa_num_attention_heads", "num_attention_heads"),
        ("layernorm_epsilon", "rms_norm_eps"))


def checked(conf: dict) -> dict:
    for key, only in ONLY.items():
        if conf[key] != only:
            raise ValueError(
                f"{key} = {conf[key]!r}: this program computes {only!r} only")
    for a, b in SAME:
        if conf[a] != conf[b]:
            raise ValueError(f"{a} = {conf[a]!r} and {b} = {conf[b]!r} differ")
    n = conf["num_hidden_layers"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        if len(conf[key]) != n or set(conf[key]) - {0, 1}:
            raise ValueError(
                f"{key}: a 0 or a 1 for each of the {n} layers, not "
                f"{conf[key]!r}")
    freq = conf["moe_layer_freq"]
    if sorted(freq) != list(freq):
        raise ValueError(
            f"moe_layer_freq {freq!r}: a dense layer after an expert layer "
            "needs a program that places one")
    if conf["routed_scaling_factor"] not in (None, 1, 1.0):
        raise ValueError("a routed scaling factor needs a program that scales")
    if conf["run"].get("selection_bias", "seeded") not in ("seeded", "zero"):
        raise ValueError(f"selection_bias is seeded or zero, not "
                         f"{conf['run']['selection_bias']!r}")
    return conf


def model_config(conf: dict, **overrides):
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.lora import LoRAConfig

    conf = checked(conf)
    run = conf["run"]
    held, total = conf["n_routed_experts"], published_experts(conf)
    kw: dict[str, Any] = dict(
        vocab_size=conf["vocab_size"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        layer_pattern="".join("FW"[kind] for kind in conf["hybrid_layer_pattern"]),
        first_k_dense=conf["moe_layer_freq"].count(0),
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim_override=conf["head_dim"],
        v_head_dim=conf["v_head_dim"],
        rotary_dim=int(conf["head_dim"] * conf["partial_rotary_factor"]),
        rope_theta=float(conf["rope_theta"]),
        attention_value_scale=float(conf["attention_value_scale"]),
        sliding_window=conf["sliding_window"],
        window_kv_heads=conf["swa_num_key_value_heads"],
        window_rope_theta=float(conf["swa_rope_theta"]),
        window_sink=bool(conf["add_swa_attention_sink_bias"]),
        d_ff=conf["intermediate_size"],
        rms_eps=float(conf["layernorm_epsilon"]),
        max_seq_len=int(run["max_seq_len"]),
        n_experts=total,
        experts_held=(0, held) if held != total else None,
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_shared_experts=0,
        moe_scoring="sigmoid",
        moe_dispatch="dropless",
        # noaux_tc: balanced by a frozen selection bias, no auxiliary loss; a
        # run that holds the bias at zero builds the layer without the leaf
        moe_select_bias=(conf["topk_method"] == "noaux_tc"
                         and run.get("selection_bias", "seeded") == "seeded"),
        router_aux_weight=0.0,
        moe_routed_scale=1.0,
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
