"""``trace_table.py`` for a cell of the sparse / lightning program: seconds of
one traced step by scope and by pass (PERF.md section 5) under that program's
scopes — the selection's ``sparse_compress``, ``sparse_block_scores`` and
``sparse_block_topk`` and the lightning layers' ``ssd_scan`` first, then the
projections' ``base_matmul`` / ``lora_delta``, and what else lies under each
kind's module name (``sparse_attn``, ``lightning``) — then the same step BY
LAYER KIND and pass: a layer's kind reads from the name stack (its mixer's
module name; a block's norms and its MLP carry none and stay with ``mlp`` and
``other``).

    python3 benchmarks/tools/trace_table_minicpm_sala.py --workload <cell> [--steps 2]

Not part of a benchmark run.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.tools import trace_table  # noqa: E402

at = trace_table.SCOPES.index("attn")
trace_table.SCOPES = (
    "sparse_compress", "sparse_block_scores", "sparse_block_topk", "ssd_scan",
    *trace_table.SCOPES[:at], "sparse_attn", "lightning", *trace_table.SCOPES[at:])
#: the second table's rows: the two kinds by their mixer's module name, the
#: MLP, then the head
trace_table.PROJECTIONS = ("sparse_attn", "lightning", "mlp", "lm_head")

if __name__ == "__main__":
    trace_table.main()
