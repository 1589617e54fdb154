"""The pattern program: ``LlamaConfig`` — the ONE decoder of
``models/llama.py`` — from a configuration file's PUBLISHED keys for a model
that is a PATTERN of single-mixer layers.  ``hybrid_override_pattern`` places
them, one letter a layer, each ONE norm, ONE mixer and one residual add with
no MLP half: ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
``mamba_head_dim`` over a ``ssm_state_size``-wide state, B and C in
``n_groups`` groups, a convolution of ``conv_kernel`` rows, chunks of
``chunk_size``), ``E`` an expert layer (sigmoid top-``num_experts_per_tok`` of
the published ``n_routed_experts`` squared-ReLU experts WITHOUT a gate of width
``moe_intermediate_size`` in a ``moe_latent_size``-wide latent, the router and
a shared expert of ``moe_shared_expert_intermediate_size`` on the
``hidden_size``-wide state), ``*`` grouped-query attention WITHOUT positions.
A file that holds a share of the experts (``n_routed_experts`` in ``reduced``)
routes over the published count and computes its own: ``experts_held = (0,
n_routed_experts)``.  ``run.selection_bias`` (``"seeded"`` | ``"zero"``) as
``programs/mla_moe.py`` reads it.  Found by name (``"program":
"nemotron_h"``).  Refuses what it does not compute."""

from __future__ import annotations

from typing import Any

from benchmarks.harness.programs.mla_dsa_moe import published_experts

#: a published key -> the one value this program computes
ONLY = {
    "model_type": "nemotron_h", "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
    "mamba_proj_bias": False, "use_bias": False, "use_conv_bias": True,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "tie_word_embeddings": False, "residual_in_fp32": False,
    "sliding_window": None, "moe_shared_expert_overlap": False,
    "num_nextn_predict_layers": 0,
}
LETTERS = "ME*"


def checked(conf: dict) -> dict:
    for key, only in ONLY.items():
        if conf[key] != only:
            raise ValueError(
                f"{key} = {conf[key]!r}: this program computes {only!r} only")
    pattern = conf["hybrid_override_pattern"]
    if set(pattern) - set(LETTERS) or len(pattern) != conf["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: one of {' '.join(LETTERS)} "
            f"for each of the {conf['num_hidden_layers']} layers (a '-' layer, "
            "an MLP by itself, needs a program that holds one)")
    if conf["expand"] * conf["hidden_size"] != (
            conf["mamba_num_heads"] * conf["mamba_head_dim"]):
        raise ValueError("expand x hidden_size is not mamba_num_heads heads "
                         "of mamba_head_dim")
    if conf["mamba_num_heads"] % conf["n_groups"]:
        raise ValueError("n_groups does not divide mamba_num_heads")
    if conf["moe_shared_expert_intermediate_size"] % conf["moe_intermediate_size"]:
        raise ValueError("the shared expert is not a whole number of routed "
                         "experts wide")
    if not (conf["layer_norm_epsilon"] == conf["norm_eps"] == conf["rms_norm_eps"]):
        raise ValueError("layer_norm_epsilon, norm_eps and rms_norm_eps differ")
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
        layer_pattern=conf["hybrid_override_pattern"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        # no rotary embedding: rope_theta and partial_rotary_factor stand in
        # the source and are read by nothing (the file's ``assumed``)
        rope_theta=0.0,
        rms_eps=float(conf["layer_norm_epsilon"]),
        max_seq_len=int(run["max_seq_len"]),
        mlp_act="relu2",
        ssm_n_heads=conf["mamba_num_heads"],
        ssm_head_dim=conf["mamba_head_dim"],
        ssm_d_state=conf["ssm_state_size"],
        ssm_n_groups=conf["n_groups"],
        ssm_d_conv=conf["conv_kernel"],
        ssm_chunk=conf["chunk_size"],
        n_experts=total,
        experts_held=(0, held) if held != total else None,
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_latent=conf["moe_latent_size"],
        # ONE shared MLP of the published width, in routed experts' widths
        n_shared_experts=conf["n_shared_experts"] * (
            conf["moe_shared_expert_intermediate_size"]
            // conf["moe_intermediate_size"]),
        moe_scoring="sigmoid",
        moe_dispatch="dropless",
        # balanced by a frozen selection bias, no auxiliary loss; a run that
        # holds the bias at zero builds the layer without the leaf
        moe_select_bias=run.get("selection_bias", "seeded") == "seeded",
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
    if conf["hidden_size"] // conf["num_attention_heads"] != conf["head_dim"]:
        kw["head_dim_override"] = conf["head_dim"]
    kw.update(overrides)
    return LlamaConfig(**kw)
