"""Operations and bytes of the pattern model, computed from the configuration
file's published keys — what ``harness/counts.py`` cannot give: three kinds of
single-mixer layer placed by ``hybrid_override_pattern`` (each counted as
often as its letter stands there), experts WITHOUT a gate in a latent (two
matrices of ``moe_latent_size x moe_intermediate_size`` an expert) of which
this chip holds a share, and the state-space recurrence at this family's head
shape.  As there: a multiply-add is 2 FLOPs, recomputed operations never
count, a frozen matrix needs 2 + 2 FLOPs a weight and an adapter matrix 6, and
only what a token TOUCHES HERE counts (a token's ``num_experts_per_tok``
choices fall on this chip's experts ``held / published`` of the time).

**What the recurrence NEEDS, whatever computes it**, as
``counting/falcon_h1.py`` counts it: in its chunked form at the published
chunk ``Q``, a token, a layer, forward ``2 Q N G + 2 Q P H + 4 N P H``, three
times a step; ``x``, ``B``, ``C``, ``delta`` read and ``y`` written once a pass
in the compute type."""

from __future__ import annotations

from benchmarks.harness import counts
from benchmarks.harness.counting.mla_dsa_moe import held_share, routed_width

#: forward + the backward pass's two products for each of the forward's
PASSES = 3


def layers(conf: dict, kind: str) -> int:
    """How many layers of ``kind`` (``M`` | ``E`` | ``*``) the file runs."""
    return conf["hybrid_override_pattern"].count(kind)


def proj_shapes(conf: dict) -> dict[str, dict[str, tuple[int, int]]]:
    """The adapted projections of a layer by its kind, ``name -> (in, out)``."""
    d, hd = conf["hidden_size"], conf["head_dim"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    inner = conf["mamba_num_heads"] * conf["mamba_head_dim"]
    channels = inner + 2 * conf["n_groups"] * conf["ssm_state_size"]
    latent = conf["moe_latent_size"]
    shared = conf["n_shared_experts"] * conf["moe_shared_expert_intermediate_size"]
    return {
        "*": {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
              "o_proj": (q, d)},
        "M": {"in_proj": (d, inner + channels + conf["mamba_num_heads"]),
              "out_proj": (inner, d)},
        "E": {"fc1_latent_proj": (d, latent), "fc2_latent_proj": (latent, d),
              "up_proj": (d, shared), "down_proj": (shared, d)},
    }


def expert_params(conf: dict) -> int:
    """One routed expert: ``up`` and ``down`` in the latent, no gate."""
    return 2 * conf["moe_latent_size"] * conf["moe_intermediate_size"]


def layer_active_params(conf: dict, kind: str) -> float:
    """Frozen matmul weights one token is multiplied by HERE in a layer of
    ``kind``: its projections and, in an expert layer, the router at its
    published width and its choices' share of the routed experts."""
    weights = float(sum(i * o for i, o in proj_shapes(conf)[kind].values()))
    if kind == "E":
        weights += (conf["hidden_size"] * routed_width(conf)
                    + conf["num_experts_per_tok"] * held_share(conf)
                    * expert_params(conf))
    return weights


def frozen_active_params(conf: dict) -> float:
    """Every frozen matrix a token is multiplied by here: the layers' by kind
    and the head's slice (the embedding is a lookup)."""
    return (sum(layers(conf, kind) * layer_active_params(conf, kind)
                for kind in "ME*")
            + conf["hidden_size"] * conf["vocab_size"])


def lora_params(conf: dict) -> int:
    r, targets = conf["run"]["lora_rank"], conf["run"]["lora_targets"]
    return sum(layers(conf, kind) * r * (i + o)
               for kind, shapes in proj_shapes(conf).items()
               for name, (i, o) in shapes.items() if name in targets)


def held_expert_flops_per_token(conf: dict) -> float:
    """One token through the routed experts HELD here, every expert layer:
    forward and activation-gradient products (2 + 2 FLOPs a weight) of the
    ``held_share`` of its ``num_experts_per_tok`` choices."""
    return (4.0 * layers(conf, "E") * conf["num_experts_per_tok"]
            * held_share(conf) * expert_params(conf))


def scan_flops_per_token_layer(conf: dict) -> int:
    """The recurrence of ONE token in ONE layer, forward, in chunks of the
    published size."""
    q, n, g = conf["chunk_size"], conf["ssm_state_size"], conf["n_groups"]
    p, h = conf["mamba_head_dim"], conf["mamba_num_heads"]
    return 2 * q * n * g + 2 * q * p * h + 4 * n * p * h


def scan_flops_per_token(conf: dict) -> float:
    """The recurrence of one training token, every ``M`` layer, all three
    passes."""
    return float(PASSES * layers(conf, "M") * scan_flops_per_token_layer(conf))


def scan_bytes_per_token(conf: dict, itemsize: int = 2) -> float:
    """``x``, ``B``, ``C``, ``delta`` read and ``y`` written, once a pass."""
    row = (2 * conf["mamba_num_heads"] * conf["mamba_head_dim"]
           + 2 * conf["n_groups"] * conf["ssm_state_size"]
           + conf["mamba_num_heads"])
    return float(PASSES * layers(conf, "M") * row * itemsize)


def lora_train_flops_per_token(conf: dict, seq: int) -> float:
    """Required work of one LoRA training token of the WHOLE step: 4 x the
    frozen matmul weights it touches here + 6 x the adapters + causal
    attention forward and twice that backward in the ``*`` layers + the
    recurrence likewise in the ``M`` layers."""
    attn = (PASSES * counts.attention_flops_fwd(conf, seq)
            * layers(conf, "*") / seq)
    return (4.0 * frozen_active_params(conf) + 6.0 * lora_params(conf)
            + attn + scan_flops_per_token(conf))
