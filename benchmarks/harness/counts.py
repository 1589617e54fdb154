"""Operations and bytes computed from shapes — the benchmark's own count.

All functions take the configuration file's dict (published keys + ``run``)
and plain sizes, and return FLOPs (a multiply-add is 2) or bytes.  Recomputed
operations (rematerialisation) never count towards a utilisation; a kernel's
roofline counts what each CALL of the kernel needs.
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device kind.  A kind that is not in the
    table is an error, never a default."""
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    kind = device_kind.lower()
    for row in table["devices"]:
        if any(m in kind for m in row["match"]):
            return row
    raise KeyError(f"no published peaks for device kind {device_kind!r}")


def head_dim(conf: dict) -> int:
    return conf.get("head_dim", conf["hidden_size"] // conf["num_attention_heads"])


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer's seven projections."""
    d, f, hd = conf["hidden_size"], conf["intermediate_size"], head_dim(conf)
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def frozen_matmul_params(conf: dict) -> int:
    """Every frozen matrix a token is multiplied by: the layers' projections
    and the output head (the embedding is a lookup)."""
    return (conf["num_hidden_layers"] * layer_matmul_params(conf)
            + conf["hidden_size"] * conf["vocab_size"])


def lora_params(conf: dict) -> int:
    r = conf["run"]["lora_rank"]
    d, f, hd = conf["hidden_size"], conf["intermediate_size"], head_dim(conf)
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    shapes = {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
              "o_proj": (q, d), "gate_proj": (d, f), "up_proj": (d, f),
              "down_proj": (f, d)}
    per_layer = sum(r * (i + o) for n, (i, o) in shapes.items()
                    if n in conf["run"]["lora_targets"])
    return conf["num_hidden_layers"] * per_layer


def attention_flops_fwd(conf: dict, seq: int, causal: bool = True) -> float:
    """QK^T and PV of ONE sequence in ONE layer: 4 * S^2 * heads * head_dim,
    halved for the causal triangle."""
    full = 4.0 * seq * seq * conf["num_attention_heads"] * head_dim(conf)
    return full / 2 if causal else full


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA/QLoRA training token.  A frozen matrix needs
    its forward product and the activation-gradient product (2 + 2 FLOPs a
    weight) but NO weight-gradient product; an adapter matrix needs all three
    (6); causal attention needs forward plus twice that backward, averaged
    over the sequence's positions."""
    n_layers = conf["num_hidden_layers"]
    attn = 3.0 * attention_flops_fwd(conf, seq) * n_layers / seq
    return 4.0 * frozen_matmul_params(conf) + 6.0 * lora_params(conf) + attn


def flash_call_flops(conf: dict, batch: int, seq: int, kind: str) -> float:
    """What one call of a flash kernel needs for ``batch`` sequences of one
    layer: forward 2 matmuls (QK^T, PV); the dQ kernel 3 (recomputed scores,
    dP, dQ); the dK/dV kernel 4 (scores, dV, dP, dK) — each 2*S^2*H*D over
    the causal half."""
    unit = attention_flops_fwd(conf, seq) / 2.0 * batch
    return {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind] * unit


def flash_call_bytes(conf: dict, batch: int, seq: int, kind: str,
                     itemsize: int = 2) -> float:
    """HBM traffic one call needs: Q, K, V read and the outputs written once
    (the backward kernels also read dO, O-stats)."""
    hd = head_dim(conf)
    q = batch * seq * conf["num_attention_heads"] * hd * itemsize
    kv = batch * seq * conf["num_key_value_heads"] * hd * itemsize
    return {"fwd": 2 * q + 2 * kv, "bwd_dq": 3 * q + 2 * kv,
            "bwd_dkv": 2 * q + 4 * kv}[kind]


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Keys and values of one token in ONE layer."""
    return 2 * conf["num_key_value_heads"] * head_dim(conf) * itemsize


def paged_decode_call_bytes(conf: dict, live_tokens: int) -> float:
    """What one decode call of the paged kernel (one layer) must read: the
    LIVE keys and values of every active lane."""
    return float(live_tokens) * kv_bytes_per_token(conf)


def paged_decode_call_flops(conf: dict, live_tokens: int) -> float:
    return 4.0 * live_tokens * conf["num_attention_heads"] * head_dim(conf)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tc, tm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
