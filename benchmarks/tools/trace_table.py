"""Seconds of one traced step by the program's own scopes and by pass: the
table of PERF.md section 5, from the trace a ``--trace 1`` run of a cell
left under ``.cache/benchmarks/<cell>/trace``.

    python3 benchmarks/tools/trace_table.py --workload <cell> [--steps 2]

An operation is charged whole to the FIRST of ``SCOPES`` its name stack
holds (a fusion carries the stack of the instruction it was built around),
to ``other`` if none.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import scopes as S, trace as T  # noqa: E402

SCOPES = ("dequant_int4", "lora_delta", "base_matmul", "flash_fwd",
          "flash_bwd_dq", "flash_bwd_dkv", "rope", "attn_norm", "mlp_norm",
          "loss", "final_norm", "lm_head", "embed_tokens", "optimizer",
          "attn", "mlp")
PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
               "down_proj", "lm_head")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args()
    scratch = ROOT / ".cache" / "benchmarks" / args.workload
    tr = T.load(str(scratch / "trace"))
    run = types.SimpleNamespace(trace=tr, traced=T.window(tr), scratch=scratch)
    ops = [op for chip in S.step_ops(run) for op in chip]
    chips = max(1, len(tr.devices))
    step = (run.traced[1] - run.traced[0]) / args.steps
    print(f"{args.workload}: traced window {run.traced[1] - run.traced[0]:.4f} s, "
          f"{args.steps} step(s) of {step:.4f} s, {chips} chip(s)")
    passes = (*S.PASSES, None)

    def table(rows, key):
        sums = {r: dict.fromkeys(passes, 0.0) for r in rows}
        for op in ops:
            row = key(op)
            if row is not None:
                sums[row][op.which_pass] += op.seconds / chips / args.steps
        print(f"{'':14}" + "".join(f"{p or 'no pass':>11}" for p in passes)
              + f"{'% of step':>11}")
        for r in rows:
            total = sum(sums[r].values())
            if total > 0.0:
                print(f"{r:14}" + "".join(f"{sums[r][p]:11.4f}" for p in passes)
                      + f"{100 * total / step:11.2f}")

    table((*SCOPES, "other"),
          lambda op: next((s for s in SCOPES if s in op.names), "other"))
    print()
    table(PROJECTIONS,
          lambda op: next((p for p in PROJECTIONS if p in op.names), None))


if __name__ == "__main__":
    main()
