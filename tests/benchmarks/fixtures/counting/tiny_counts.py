"""Tests only: a module of counts added by a new file alone — an
architecture whose q/k heads (``qk_head_dim``) and v heads (``v_head_dim``)
differ in size, which ``harness/counts.py`` cannot count."""


def train_flops_per_token(conf: dict, seq: int) -> float:
    return 1000.0 * conf["hidden_size"] + seq


def proj_flops_per_token(conf: dict) -> float:
    return 500.0 * conf["hidden_size"]


def flash_call_flops(conf: dict, batch: int, seq: int, kind: str) -> float:
    """QK^T over ``qk_head_dim``, PV over ``v_head_dim``, causal half."""
    heads, qk, v = (conf["num_attention_heads"], conf["qk_head_dim"],
                    conf["v_head_dim"])
    scores, values = (seq * seq * heads * d * batch for d in (qk, v))
    return {"fwd": scores + values, "bwd_dq": 2 * scores + values,
            "bwd_dkv": 2 * scores + 2 * values}[kind]


def flash_call_bytes(conf: dict, batch: int, seq: int, kind: str) -> float:
    heads, qk, v = (conf["num_attention_heads"], conf["qk_head_dim"],
                    conf["v_head_dim"])
    return 2.0 * batch * seq * heads * (2 * qk + 2 * v)
