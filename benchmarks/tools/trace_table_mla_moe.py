"""``trace_table.py`` for a cell of the latent-attention expert program: the
same table (seconds of one traced step by scope and by pass, PERF.md section
5) under that program's scopes — the expert layer's ``moe_route``,
``moe_dispatch``, ``experts``, ``moe_combine`` and ``shared`` first, then the
projections' parts, the flash kernels and the rest.

    python3 benchmarks/tools/trace_table_mla_moe.py --workload <cell> [--steps 2]

Not part of a benchmark run.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.tools import trace_table  # noqa: E402

trace_table.SCOPES = (
    "moe_route", "moe_dispatch", "experts", "moe_combine", "shared",
    "lora_delta", "base_matmul", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    "rope", "q_a_norm", "kv_a_norm", "attn_norm", "mlp_norm", "loss",
    "final_norm", "lm_head", "embed_tokens", "optimizer", "moe", "attn", "mlp")
trace_table.PROJECTIONS = (
    "q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj", "lm_head")

if __name__ == "__main__":
    trace_table.main()
