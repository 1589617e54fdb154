"""Operations a named part of the train step NEEDS, computed from shapes —
the counts behind the ``scope_roofline`` metrics, beside ``counts.py``.
Recomputed operations never count: rematerialisation shows as time."""

from __future__ import annotations

from benchmarks.harness import counts


def proj_matmul_flops_per_token(conf: dict) -> float:
    """One LoRA/QLoRA training token through the layers' seven projections:
    a frozen matrix needs its forward product and the activation-gradient
    product (2 + 2 FLOPs a weight, no weight gradient), an adapter matrix
    all three (6).  The output head is not a layer projection."""
    return (4.0 * conf["num_hidden_layers"] * counts.layer_matmul_params(conf)
            + 6.0 * counts.lora_params(conf))
