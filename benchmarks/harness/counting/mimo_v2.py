"""Operations and bytes of the window/full-attention expert model, computed
from the configuration file's published keys — what ``harness/counts.py``
cannot give: two kinds of attention placed by ``hybrid_layer_pattern`` (each
counted as often as its number stands there), q/k heads of ``head_dim`` beside
v heads of ``v_head_dim``, a window layer's attention at the pairs its window
NEEDS (``sum_t min(t + 1, sliding_window)`` a row and head, whatever a kernel
computes), a leading dense layer, and experts of which this chip holds a
share.  As there: a multiply-add is 2 FLOPs, recomputed operations never
count, a frozen matrix needs 2 + 2 FLOPs a weight and an adapter matrix 6, and
only what a token TOUCHES HERE counts (a token's ``num_experts_per_tok``
choices fall on this chip's experts ``held / published`` of the time)."""

from __future__ import annotations

from benchmarks.harness.counting.mla_dsa_moe import held_share, routed_width

#: forward + the backward pass's two products for each of the forward's
PASSES = 3
FULL, WINDOW = 0, 1


def layers(conf: dict, kind: int) -> int:
    """How many layers of attention ``kind`` (``FULL`` | ``WINDOW``) run."""
    return conf["hybrid_layer_pattern"].count(kind)


def expert_layers(conf: dict) -> int:
    return conf["moe_layer_freq"].count(1)


def kv_heads(conf: dict, kind: int) -> int:
    return conf["swa_num_key_value_heads" if kind else "num_key_value_heads"]


def attn_shapes(conf: dict, kind: int) -> dict[str, tuple[int, int]]:
    """The four projections of a layer of ``kind``, ``name -> (in, out)``."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    qk, v, kv = conf["head_dim"], conf["v_head_dim"], kv_heads(conf, kind)
    return {"q_proj": (d, h * qk), "k_proj": (d, kv * qk),
            "v_proj": (d, kv * v), "o_proj": (h * v, d)}


def mlp_shapes(conf: dict) -> dict[str, tuple[int, int]]:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    return {"gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}


def _weights(shapes: dict) -> int:
    return sum(i * o for i, o in shapes.values())


def expert_params(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def frozen_active_params(conf: dict) -> float:
    """Every frozen matrix a token is multiplied by here: both kinds'
    projections, the dense layers' MLP, in an expert layer the router at its
    published width and its choices' share of the routed experts, and the
    head's slice (the embedding is a lookup)."""
    dense = conf["num_hidden_layers"] - expert_layers(conf)
    return (sum(layers(conf, kind) * _weights(attn_shapes(conf, kind))
                for kind in (FULL, WINDOW))
            + dense * _weights(mlp_shapes(conf))
            + expert_layers(conf) * (
                conf["hidden_size"] * routed_width(conf)
                + conf["num_experts_per_tok"] * held_share(conf)
                * expert_params(conf))
            + conf["hidden_size"] * conf["vocab_size"])


def lora_params(conf: dict) -> int:
    r, targets = conf["run"]["lora_rank"], conf["run"]["lora_targets"]

    def adapters(shapes):
        return sum(r * (i + o) for n, (i, o) in shapes.items() if n in targets)

    dense = conf["num_hidden_layers"] - expert_layers(conf)
    return (sum(layers(conf, kind) * adapters(attn_shapes(conf, kind))
                for kind in (FULL, WINDOW))
            + dense * adapters(mlp_shapes(conf)))


def pairs(conf: dict, seq: int, kind: int) -> float:
    """(query, key) pairs one head of one row NEEDS: the causal triangle's
    ``S^2 / 2`` in a full layer (the count ``harness/counts.py`` charges), a
    window layer's ``sum_t min(t + 1, sliding_window)``."""
    if kind == WINDOW:
        w = min(conf["sliding_window"], seq)
        return w * (w + 1) / 2.0 + (seq - w) * float(w)
    return seq * seq / 2.0


def attention_flops_fwd(conf: dict, seq: int, kind: int) -> float:
    """QK^T over the q/k head size and PV over the v head size of ONE row in
    ONE layer of ``kind``, at the pairs it needs."""
    return (2.0 * pairs(conf, seq, kind) * conf["num_attention_heads"]
            * (conf["head_dim"] + conf["v_head_dim"]))


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA training token of the WHOLE step: 4 x the
    frozen matmul weights it touches here + 6 x the adapters + attention
    forward and twice that backward, each layer at ITS kind's pairs (a window
    layer at its window's: a program that computes more earns nothing)."""
    attn = sum(PASSES * attention_flops_fwd(conf, seq, kind) * layers(conf, kind)
               for kind in (FULL, WINDOW)) / seq
    return (4.0 * frozen_active_params(conf) + 6.0 * lora_params(conf) + attn)


def held_expert_flops_per_token(conf: dict) -> float:
    """One token through the routed experts HELD here, every expert layer:
    forward and activation-gradient products (2 + 2 FLOPs a weight) of the
    ``held_share`` of its ``num_experts_per_tok`` choices."""
    return (4.0 * expert_layers(conf) * conf["num_experts_per_tok"]
            * held_share(conf) * expert_params(conf))


def _call(kind: str) -> tuple[int, str]:
    """``"swa_bwd_dq"`` -> ``(WINDOW, "bwd_dq")``; ``"fwd"`` -> ``(FULL, "fwd")``."""
    return (WINDOW, kind[4:]) if kind.startswith("swa_") else (FULL, kind)


def flash_call_flops(conf: dict, batch: int, seq: int, kind: str) -> float:
    """What one call of a flash kernel needs for ``batch`` rows of one layer
    at the pairs of the layer's kind (``fwd`` ...: a full layer's causal
    triangle; ``swa_fwd`` ...: a window layer's window): a product over the q/k
    head size (scores, dQ, dK) is 2 x pairs x H x qk, one over the v head size
    (PV, dP, dV) 2 x pairs x H x v.  Forward: scores + PV; dQ kernel: scores,
    dP, dQ; dK/dV kernel: scores, dV, dP, dK."""
    layer, call = _call(kind)
    unit = 2.0 * pairs(conf, seq, layer) * conf["num_attention_heads"] * batch
    qk, v = unit * conf["head_dim"], unit * conf["v_head_dim"]
    return {"fwd": qk + v, "bwd_dq": 2 * qk + v, "bwd_dkv": 2 * qk + 2 * v}[call]


def flash_call_bytes(conf: dict, batch: int, seq: int, kind: str,
                     itemsize: int = 2) -> float:
    """HBM traffic one call needs: Q and dQ at the query heads, K, V and
    their cotangents at the KIND's key/value heads, the output and its
    cotangent at the v head size, each read or written once."""
    layer, call = _call(kind)
    rows = batch * seq * itemsize
    h, kv = conf["num_attention_heads"], kv_heads(conf, layer)
    q, o = rows * h * conf["head_dim"], rows * h * conf["v_head_dim"]
    k, v = rows * kv * conf["head_dim"], rows * kv * conf["v_head_dim"]
    return {"fwd": q + k + v + o,                  # q, k, v in; o out
            "bwd_dq": 2 * q + k + v + o,           # q, k, v, do in; dq out
            "bwd_dkv": q + 2 * k + 2 * v + o}[call]  # k, v, q, do in; dk, dv out
