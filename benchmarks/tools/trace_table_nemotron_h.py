"""``trace_table.py`` for a cell of the pattern program: seconds of one traced
step by scope and by pass (PERF.md section 5) under that program's scopes —
the mixer's ``ssd_scan``, ``ssm_conv`` and ``ssm_gate_norm`` and the expert
layer's ``experts``, ``moe_route``, ``moe_dispatch`` and ``moe_combine`` first,
then the projections' ``base_matmul`` / ``lora_delta``, and what else lies
under each kind's module name (``mamba``, ``moe``, ``attn``) — then the same
step BY LAYER KIND and pass: a layer's kind reads from the name stack (its
mixer's module name; the layer's own ``norm`` carries none and stays with
``other``).

    python3 benchmarks/tools/trace_table_nemotron_h.py --workload <cell> [--steps 2]

Not part of a benchmark run.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.tools import trace_table  # noqa: E402

at = trace_table.SCOPES.index("attn")
trace_table.SCOPES = (
    "ssd_scan", "ssm_conv", "ssm_gate_norm", "experts", "moe_route",
    "moe_dispatch", "moe_combine", *trace_table.SCOPES[:at], "mamba", "moe",
    *trace_table.SCOPES[at:])
#: the second table's rows: the three kinds by their mixer's module name,
#: then the head
trace_table.PROJECTIONS = ("mamba", "moe", "attn", "lm_head")

if __name__ == "__main__":
    trace_table.main()
