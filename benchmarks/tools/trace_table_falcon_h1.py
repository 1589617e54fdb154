"""``trace_table.py`` for a cell of the hybrid state-space program: the same
table (seconds of one traced step by scope and by pass, PERF.md section 5)
under that program's scopes — the mixer's ``ssd_scan``, ``ssm_conv`` and
``ssm_gate_norm`` first, what else lies under ``mamba`` after the projections'
``base_matmul`` / ``lora_delta`` — and its two projections among the rows of
the second table.

    python3 benchmarks/tools/trace_table_falcon_h1.py --workload <cell> [--steps 2]

Not part of a benchmark run.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.tools import trace_table  # noqa: E402

at = trace_table.SCOPES.index("attn")
trace_table.SCOPES = ("ssd_scan", "ssm_conv", "ssm_gate_norm",
                      *trace_table.SCOPES[:at], "mamba", *trace_table.SCOPES[at:])
trace_table.PROJECTIONS = ("in_proj", "out_proj", *trace_table.PROJECTIONS)

if __name__ == "__main__":
    trace_table.main()
