"""Operations and bytes of the latent-attention expert model, computed from
the configuration file's published keys — the counts ``harness/counts.py``
cannot give (it counts one head size and a dense MLP).  A multiply-add is 2
FLOPs.  Recomputed operations never count; a frozen matrix needs its forward
product and the activation-gradient product (2 + 2 FLOPs a weight, no weight
gradient), an adapter matrix all three (6).  Only what a token TOUCHES
counts: its ``num_experts_per_tok`` routed experts, not the ones held."""

from __future__ import annotations


def _mla_shapes(conf: dict) -> dict[str, tuple[int, int]]:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return {
        "q_a_proj": (d, conf["q_lora_rank"]),
        "q_b_proj": (conf["q_lora_rank"], h * qk),
        "kv_a_proj_with_mqa": (d, conf["kv_lora_rank"] + conf["qk_rope_head_dim"]),
        "kv_b_proj": (conf["kv_lora_rank"],
                      h * (conf["qk_nope_head_dim"] + conf["v_head_dim"])),
        "o_proj": (h * conf["v_head_dim"], d),
    }


def _mlp_shapes(d: int, f: int) -> dict[str, tuple[int, int]]:
    return {"gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}


def _weights(shapes: dict) -> int:
    return sum(i * o for i, o in shapes.values())


def _lora(conf: dict, shapes: dict) -> int:
    r = conf["run"]["lora_rank"]
    return sum(r * (i + o) for n, (i, o) in shapes.items()
               if n in conf["run"]["lora_targets"])


def _layers(conf: dict) -> tuple[int, int]:
    """(leading dense layers, expert layers) held."""
    dense = conf["first_k_dense_replace"]
    return dense, conf["num_hidden_layers"] - dense


def mla_proj_params(conf: dict) -> int:
    """Weights of one layer's five latent-attention projections."""
    return _weights(_mla_shapes(conf))


def expert_params(conf: dict) -> int:
    """Weights of ONE routed expert (gate, up, down)."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def dense_layer_active_params(conf: dict) -> int:
    return mla_proj_params(conf) + 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_layer_active_params(conf: dict) -> int:
    """Frozen matmul weights one token is multiplied by in an expert layer:
    the attention projections, the router, the shared expert and its
    ``num_experts_per_tok`` routed experts."""
    return (mla_proj_params(conf)
            + conf["hidden_size"] * conf["n_routed_experts"]
            + conf["n_shared_experts"] * expert_params(conf)
            + conf["num_experts_per_tok"] * expert_params(conf))


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def frozen_active_params(conf: dict) -> int:
    dense, moe = _layers(conf)
    return (dense * dense_layer_active_params(conf)
            + moe * expert_layer_active_params(conf) + head_params(conf))


def lora_params(conf: dict) -> int:
    d = conf["hidden_size"]
    dense, moe = _layers(conf)
    attn = _lora(conf, _mla_shapes(conf))
    shared = _lora(conf, _mlp_shapes(
        d, conf["n_shared_experts"] * conf["moe_intermediate_size"]))
    return ((dense + moe) * attn
            + dense * _lora(conf, _mlp_shapes(d, conf["intermediate_size"]))
            + moe * shared)


def attention_flops_fwd(conf: dict, seq: int) -> float:
    """QK^T over the q/k head size and PV over the v head size of ONE
    sequence in ONE layer, the causal half: S^2 * heads * (qk + v)."""
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return float(seq) * seq * conf["num_attention_heads"] * (qk + conf["v_head_dim"])


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA training token: 4 x the frozen ACTIVE matmul
    weights + 6 x the adapters + causal attention forward and twice that
    backward, averaged over the sequence's positions."""
    attn = 3.0 * attention_flops_fwd(conf, seq) * conf["num_hidden_layers"] / seq
    return 4.0 * frozen_active_params(conf) + 6.0 * lora_params(conf) + attn


def expert_matmul_flops_per_token(conf: dict) -> float:
    """One token through the ROUTED experts of every expert layer: forward
    and activation-gradient products of its ``num_experts_per_tok`` experts
    (the experts carry no adapter)."""
    _, moe = _layers(conf)
    return 4.0 * moe * conf["num_experts_per_tok"] * expert_params(conf)


def mla_proj_flops_per_token(conf: dict) -> float:
    """One token through every layer's five attention projections and their
    adapters."""
    layers = conf["num_hidden_layers"]
    return layers * (4.0 * mla_proj_params(conf)
                     + 6.0 * _lora(conf, _mla_shapes(conf)))


def flash_call_flops(conf: dict, batch: int, seq: int, kind: str) -> float:
    """What one call of a flash kernel needs for ``batch`` sequences of one
    layer, over the causal half: a product over the q/k head size (scores,
    dQ, dK) is S^2*H*qk, one over the v head size (PV, dP, dV) S^2*H*v.
    Forward: scores + PV; dQ kernel: scores, dP, dQ; dK/dV kernel: scores,
    dV, dP, dK."""
    h = conf["num_attention_heads"]
    qk = float(seq) * seq * h * (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) * batch
    v = float(seq) * seq * h * conf["v_head_dim"] * batch
    return {"fwd": qk + v, "bwd_dq": 2 * qk + v, "bwd_dkv": 2 * qk + 2 * v}[kind]


def flash_call_bytes(conf: dict, batch: int, seq: int, kind: str,
                     itemsize: int = 2) -> float:
    """HBM traffic one call needs: Q and K at the q/k head size (every head
    its own keys: the rotary part is broadcast into them), V, the output and
    its cotangent at the v head size, each read or written once."""
    rows = batch * seq * conf["num_attention_heads"] * itemsize
    qk = rows * (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"])
    v = rows * conf["v_head_dim"]
    return {"fwd": 2 * qk + 2 * v,            # q, k, v in; o out
            "bwd_dq": 3 * qk + 2 * v,         # q, k, v, do in; dq out
            "bwd_dkv": 3 * qk + 3 * v}[kind]  # k, v, q, do in; dk, dv out
