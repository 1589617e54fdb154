"""The hybrid state-space program: ``LlamaConfig`` — the ONE decoder of
``models/llama.py`` with its block's parts chosen — from a configuration
file's PUBLISHED keys: grouped-query attention at ``head_dim`` (heads that do
not make up ``hidden_size``) BESIDE a Mamba-2 mixer (``mamba_d_ssm`` channels
in ``mamba_n_heads`` heads of ``mamba_d_head``, a ``mamba_d_state``-wide state,
B and C in ``mamba_n_groups`` groups, a convolution of ``mamba_d_conv`` rows,
chunks of ``mamba_chunk_size``) under one norm, and the family's muP
multiplier on every branch.  Found by name (``"program": "falcon_h1"``).
Refuses what it does not compute."""

from __future__ import annotations

import importlib

llama = importlib.import_module("benchmarks.harness.programs.llama")

#: a published key -> the one value this program computes
ONLY = {
    "model_type": "falcon_h1", "hidden_act": "silu", "attention_bias": False,
    "mlp_bias": False, "projectors_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attn_layer_indices": None, "rope_scaling": None,
}


def checked(conf: dict) -> dict:
    for key, only in ONLY.items():
        if conf[key] != only:
            raise ValueError(
                f"{key} = {conf[key]!r}: this program computes {only!r} only")
    if conf["mamba_d_ssm"] != conf["mamba_n_heads"] * conf["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads heads of mamba_d_head")
    if conf["mamba_n_heads"] % conf["mamba_n_groups"]:
        raise ValueError("mamba_n_groups does not divide mamba_n_heads")
    if len(conf["ssm_multipliers"]) != 5 or len(conf["mlp_multipliers"]) != 2:
        raise ValueError("five ssm_multipliers (z, x, B, C, dt) and two "
                         "mlp_multipliers (gate, down)")
    return conf


def model_config(conf: dict, **overrides):
    conf = checked(conf)
    kw = dict(
        ssm_n_heads=conf["mamba_n_heads"],
        ssm_head_dim=conf["mamba_d_head"],
        ssm_d_state=conf["mamba_d_state"],
        ssm_n_groups=conf["mamba_n_groups"],
        ssm_d_conv=conf["mamba_d_conv"],
        ssm_chunk=conf["mamba_chunk_size"],
        embedding_multiplier=float(conf["embedding_multiplier"]),
        lm_head_multiplier=float(conf["lm_head_multiplier"]),
        attention_in_multiplier=float(conf["attention_in_multiplier"]),
        attention_out_multiplier=float(conf["attention_out_multiplier"]),
        key_multiplier=float(conf["key_multiplier"]),
        ssm_in_multiplier=float(conf["ssm_in_multiplier"]),
        ssm_out_multiplier=float(conf["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in conf["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in conf["mlp_multipliers"]),
    )
    kw.update(overrides)
    return llama.model_config(conf, **kw)
