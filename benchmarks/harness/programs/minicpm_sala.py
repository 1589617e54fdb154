"""The sparse / lightning hybrid program: ``LlamaConfig`` — the ONE decoder of
``models/llama.py`` — from a configuration file's PUBLISHED keys for a model
whose blocks differ by their mixer.  ``mixer_types`` places them, one entry a
layer: ``minicpm4`` (letter ``S``: grouped-query attention over
``num_key_value_heads`` heads of ``head_dim`` WITHOUT positions, where each
key/value head's group of query heads keeps ``sparse_config.topk`` blocks of
``sparse_config.block_size`` keys a query in rows longer than
``sparse_config.dense_len``) and ``lightning-attn`` (letter ``L``: linear
attention with a fixed decay a head, ``lightning_nh`` heads of
``lightning_head_dim``, every head its own keys, rotary embedding at
``rope_theta``), both with a learned norm on q and k (``qk_norm``), an output
gate and, the lightning layers, an output norm; under the family's muP scaling:
the embedding times ``scale_emb``, each residual branch times ``scale_depth /
sqrt(published num_hidden_layers)``, the normed state times ``dim_model_base /
hidden_size`` before the head.  ``sparse_config`` is not in the source's file:
the configuration's ``assumed`` says where its numbers come from.  Found by
name (``"program": "minicpm_sala"``).  Refuses what it does not compute."""

from __future__ import annotations

#: a published key -> the one value this program computes
ONLY = {
    "model_type": "minicpm_sala", "hidden_act": "silu", "attention_bias": False,
    "attn_use_rope": False, "lightning_use_rope": True,
    "lightning_scale": "1/sqrt(d)", "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "tie_word_embeddings": False,
}
LETTERS = {"minicpm4": "S", "lightning-attn": "L"}


def checked(conf: dict) -> dict:
    for key, only in ONLY.items():
        if conf[key] != only:
            raise ValueError(
                f"{key} = {conf[key]!r}: this program computes {only!r} only")
    mixers = conf["mixer_types"]
    if set(mixers) - set(LETTERS) or len(mixers) != conf["num_hidden_layers"]:
        raise ValueError(
            f"mixer_types {mixers!r}: one of {' '.join(LETTERS)} for each of "
            f"the {conf['num_hidden_layers']} layers")
    if conf["lightning_nkv"] != conf["lightning_nh"]:
        raise ValueError("lightning_nkv is not lightning_nh: this program "
                         "gives every lightning head its own keys")
    if conf["sparse_config"].get("use_nope", False):
        raise ValueError("sparse_config.use_nope: not computed")
    return conf


def model_config(conf: dict, **overrides):
    import jax.numpy as jnp

    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.lora import LoRAConfig

    conf = checked(conf)
    run, sparse = conf["run"], conf["sparse_config"]
    # the depth the residual branches are scaled for is the model's, not a cut's
    depth = conf.get("published", {}).get(
        "num_hidden_layers", conf["num_hidden_layers"])
    kw = dict(
        vocab_size=conf["vocab_size"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        layer_pattern="".join(LETTERS[m] for m in conf["mixer_types"]),
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        # the lightning layers' rotary base; the sparse layers rotate nothing
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]),
        max_seq_len=int(run["max_seq_len"]),
        sparse_topk=sparse["topk"],
        sparse_block=sparse["block_size"],
        sparse_window=sparse["window_size"],
        sparse_init_blocks=sparse["init_blocks"],
        sparse_dense_len=sparse["dense_len"],
        sparse_kernel=sparse["kernel_size"],
        sparse_stride=sparse["kernel_stride"],
        lightning_n_heads=conf["lightning_nh"],
        lightning_head_dim=conf["lightning_head_dim"],
        embedding_multiplier=float(conf["scale_emb"]),
        residual_multiplier=float(conf["scale_depth"]) / float(depth) ** 0.5,
        head_in_multiplier=conf["dim_model_base"] / conf["hidden_size"],
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
