"""Operations and bytes of the hybrid state-space model, computed from the
configuration file's published keys — what ``harness/counts.py`` cannot give:
the mixer's two projections beside attention's and the MLP's, and the
state-space recurrence.  As there: a multiply-add is 2 FLOPs, recomputed
operations never count, a frozen matrix needs 2 + 2 FLOPs a weight and an
adapter matrix 6.

**What the recurrence NEEDS, whatever computes it**, is counted in its chunked
form at the published chunk ``Q`` (the token-by-token form needs ``4 N P H`` of
it and runs nowhere near a matrix unit): a token, a layer, forward —
``2 Q N G`` (its row of ``C B^T``, a group) + ``2 Q P H`` (that row times ``delta
x``, a head) + ``2 N P H`` (its part of the chunk's state) + ``2 N P H`` (the
read of the state that entered the chunk); three times a step (forward, and
twice that on the way back, as attention is counted).  Its bytes: ``x``, ``B``,
``C``, ``delta`` read and ``y`` written once a pass in the compute type."""

from __future__ import annotations

from benchmarks.harness import counts

#: forward + the backward pass's two products for each of the forward's
PASSES = 3


def proj_shapes(conf: dict) -> dict[str, tuple[int, int]]:
    """A layer's nine projections, ``name -> (in, out)``."""
    d, f, hd = conf["hidden_size"], conf["intermediate_size"], conf["head_dim"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    inner = conf["mamba_d_ssm"]
    channels = inner + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
    return {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
            "o_proj": (q, d),
            "in_proj": (d, inner + channels + conf["mamba_n_heads"]),
            "out_proj": (inner, d),
            "gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}


def layer_matmul_params(conf: dict) -> int:
    return sum(i * o for i, o in proj_shapes(conf).values())


def mixer_proj_params(conf: dict) -> int:
    shapes = proj_shapes(conf)
    return sum(i * o for i, o in (shapes["in_proj"], shapes["out_proj"]))


def frozen_matmul_params(conf: dict) -> int:
    """Every frozen matrix a token is multiplied by: the layers' projections
    and the head's slice (the embedding is a lookup)."""
    return (conf["num_hidden_layers"] * layer_matmul_params(conf)
            + conf["hidden_size"] * conf["vocab_size"])


def lora_params(conf: dict) -> int:
    r = conf["run"]["lora_rank"]
    per_layer = sum(r * (i + o) for n, (i, o) in proj_shapes(conf).items()
                    if n in conf["run"]["lora_targets"])
    return conf["num_hidden_layers"] * per_layer


def scan_flops_per_token_layer(conf: dict) -> int:
    """The recurrence of ONE token in ONE layer, forward, in chunks of the
    published size."""
    q, n, g = conf["mamba_chunk_size"], conf["mamba_d_state"], conf["mamba_n_groups"]
    p, h = conf["mamba_d_head"], conf["mamba_n_heads"]
    return 2 * q * n * g + 2 * q * p * h + 4 * n * p * h


def scan_flops_per_token(conf: dict) -> float:
    """The recurrence of one training token, every layer, all three passes."""
    return float(PASSES * conf["num_hidden_layers"]
                 * scan_flops_per_token_layer(conf))


def scan_bytes_per_token(conf: dict, itemsize: int = 2) -> float:
    """``x``, ``B``, ``C``, ``delta`` read and ``y`` written, once a pass."""
    row = (2 * conf["mamba_d_ssm"]
           + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
           + conf["mamba_n_heads"])
    return float(PASSES * conf["num_hidden_layers"] * row * itemsize)


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA training token of the WHOLE step: 4 x the
    frozen matmul weights + 6 x the adapters + causal attention forward and
    twice that backward + the recurrence likewise."""
    attn = (PASSES * counts.attention_flops_fwd(conf, seq)
            * conf["num_hidden_layers"] / seq)
    return (4.0 * frozen_matmul_params(conf) + 6.0 * lora_params(conf)
            + attn + scan_flops_per_token(conf))
