"""Operations and bytes of the sparse / lightning hybrid, computed from the
configuration file's keys — what ``harness/counts.py`` cannot give: layers of
two kinds with unlike projections, attention over each query's SELECTED blocks
(``selected_pairs``: a query keeps ``topk`` blocks of ``block_size`` keys, its
own cut at the query), the compressed scores that make the selection, and the
lightning layers' recurrence.  As there: a multiply-add is 2 FLOPs, recomputed
operations never count, a frozen matrix needs 2 + 2 FLOPs a weight and an
adapter matrix 6.

**What the recurrence NEEDS, whatever computes it**, is counted in its chunked
form at the program's chunk ``Q`` (128: ``SCAN_CHUNK``) as ``counting/falcon_h1.py`` counts the state-space mixer's: a token, a
layer, forward — ``2 Q N G`` (its row of ``q k^T``: every head a group of its
own, ``G = H``) + ``2 Q P H`` (that row times ``v``) + ``4 N P H`` (its part
of the chunk's state and the read of the state that entered the chunk); three
times a step.  Its bytes: ``q``, ``k``, ``v`` read and ``o`` written once a
pass in the compute type.

**What a flash call of a sparse layer NEEDS** is the selected pairs' products
and the selection read once, a bit a (query, key) pair a key/value head,
whatever the kernel computes under its mask."""

from __future__ import annotations

from benchmarks.harness import counts

#: forward + the backward pass's two products for each of the forward's
PASSES = 3
#: rows a chunk of the lightning layers' scan: the program's (``LlamaConfig.
#: ssm_chunk``'s default; the source's file has no key for it)
SCAN_CHUNK = 128
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layers(conf: dict, kind: str) -> int:
    return conf["mixer_types"].count(kind)


def proj_shapes(conf: dict, kind: str) -> dict[str, tuple[int, int]]:
    """The eight projections of a layer of ``kind``, ``name -> (in, out)``."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    if kind == SPARSE:
        q = conf["num_attention_heads"] * conf["head_dim"]
        kv = conf["num_key_value_heads"] * conf["head_dim"]
    else:
        q = kv = conf["lightning_nh"] * conf["lightning_head_dim"]
    return {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
            "o_gate": (d, q), "o_proj": (q, d),
            "gate_proj": (d, f), "up_proj": (d, f), "down_proj": (f, d)}


def layer_matmul_params(conf: dict, kind: str) -> int:
    return sum(i * o for i, o in proj_shapes(conf, kind).values())


def frozen_matmul_params(conf: dict) -> int:
    """Every frozen matrix a token is multiplied by: both kinds' projections
    and the head's slice (the embedding is a lookup)."""
    return (sum(layers(conf, kind) * layer_matmul_params(conf, kind)
                for kind in (SPARSE, LIGHTNING))
            + conf["hidden_size"] * conf["vocab_size"])


def lora_params(conf: dict) -> int:
    r, targets = conf["run"]["lora_rank"], conf["run"]["lora_targets"]
    return sum(layers(conf, kind) * sum(
        r * (i + o) for n, (i, o) in proj_shapes(conf, kind).items()
        if n in targets) for kind in (SPARSE, LIGHTNING))


def proj_matmul_flops_per_token(conf: dict) -> float:
    """One training token through both kinds' eight projections (the output
    head is not a layer projection): ``scope_counts``' rule at two kinds."""
    return (4.0 * sum(layers(conf, kind) * layer_matmul_params(conf, kind)
                      for kind in (SPARSE, LIGHTNING))
            + 6.0 * lora_params(conf))


def selected_pairs(conf: dict, seq: int) -> int:
    """(query, key) pairs ONE key/value head's group attends in one sequence:
    every key at or before the query while its blocks are at most ``topk``
    (and in a row of at most ``dense_len``), else ``topk - 1`` whole blocks
    and its own up to the query."""
    sparse = conf["sparse_config"]
    block, top = sparse["block_size"], sparse["topk"]
    if seq <= sparse["dense_len"]:
        return seq * (seq + 1) // 2
    return sum(t + 1 if t // block < top else (top - 1) * block + t % block + 1
               for t in range(seq))


def selected_keys_mean(conf: dict, seq: int) -> float:
    """What the step's counter ``sparse_selected_keys_mean`` reads."""
    return selected_pairs(conf, seq) / seq


def compressed_keys_seen(conf: dict, seq: int) -> int:
    """(query, compressed key) pairs of one sequence: a query sees the windows
    that end at or before it."""
    sparse = conf["sparse_config"]
    kernel, stride = sparse["kernel_size"], sparse["kernel_stride"]
    if seq <= sparse["dense_len"]:
        return 0
    return sum(max(0, (t + 1 - kernel) // stride + 1) for t in range(seq))


def select_scores_flops_per_token(conf: dict, seq: int) -> float:
    """The compressed scores, forward once (they carry no gradient): every
    query head against the compressed keys it sees, every sparse layer,
    averaged over a row of ``seq`` tokens."""
    return (layers(conf, SPARSE) * 2.0 * conf["num_attention_heads"]
            * conf["head_dim"] * compressed_keys_seen(conf, seq) / seq)


def attention_flops_fwd(conf: dict, seq: int) -> float:
    """QK^T and PV of ONE sequence in ONE sparse layer over the selection."""
    return (4.0 * selected_pairs(conf, seq) * conf["num_attention_heads"]
            * conf["head_dim"])


def scan_flops_per_token_layer(conf: dict) -> int:
    """The recurrence of ONE token in ONE lightning layer, forward."""
    q, h = SCAN_CHUNK, conf["lightning_nh"]
    n = p = conf["lightning_head_dim"]
    return 2 * q * n * h + 2 * q * p * h + 4 * n * p * h


def scan_flops_per_token(conf: dict) -> float:
    """The recurrence of one training token, every lightning layer, all three
    passes."""
    return float(PASSES * layers(conf, LIGHTNING)
                 * scan_flops_per_token_layer(conf))


def scan_bytes_per_token(conf: dict, itemsize: int = 2) -> float:
    """``q``, ``k``, ``v`` read and ``o`` written, once a pass."""
    row = 4 * conf["lightning_nh"] * conf["lightning_head_dim"]
    return float(PASSES * layers(conf, LIGHTNING) * row * itemsize)


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA training token of the WHOLE step: 4 x the
    frozen matmul weights + 6 x the adapters + attention over the SELECTED
    pairs forward and twice that backward in the sparse layers + the
    compressed scores once + the recurrence three times in the lightning
    layers."""
    attn = PASSES * attention_flops_fwd(conf, seq) * layers(conf, SPARSE) / seq
    return (4.0 * frozen_matmul_params(conf) + 6.0 * lora_params(conf) + attn
            + select_scores_flops_per_token(conf, seq)
            + scan_flops_per_token(conf))


def flash_call_flops(conf: dict, batch: int, seq: int, kind: str) -> float:
    """What one call of a flash kernel needs for ``batch`` sequences of one
    sparse layer over the SELECTED pairs, whatever the kernel computes:
    forward scores + PV; dQ kernel scores, dP, dQ; dK/dV kernel scores, dV,
    dP, dK — each ``2 x pairs x heads x head_dim``."""
    unit = attention_flops_fwd(conf, seq) / 2.0 * batch
    return {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind] * unit


def flash_call_bytes(conf: dict, batch: int, seq: int, kind: str,
                     itemsize: int = 2) -> float:
    """The dense call's traffic (Q, K, V, output and cotangents once) plus the
    selection read once: a bit a (query, key) pair a key/value head."""
    sets = (conf["num_key_value_heads"]
            if seq > conf["sparse_config"]["dense_len"] else 0)
    return (counts.flash_call_bytes(conf, batch, seq, kind, itemsize)
            + batch * sets * seq * seq / 8)
