"""``trace_table.py`` for a cell of the window/full-attention expert program:
seconds of one traced step by scope and by pass (PERF.md section 5) under that
program's scopes — the window calls' kernels by their own Pallas names
(``flash_swa_fwd``, ``flash_swa_bwd_dq``, ``flash_swa_bwd_dkv``) and the sink's
per-row terms (``attn_sink``) beside the full layers' ``flash_*``, the expert
layer's ``experts``, ``moe_route``, ``moe_dispatch`` and ``moe_combine`` — then
the same step BY ATTENTION KIND and pass: a layer's kind reads from where the
pattern placed it (``KINDS``: the cut's full layers are ``layer_0`` and
``layer_5``, its window layers the stack ``blocks`` and ``layer_6``; a stacked
layer's stack holds ``blocks`` AND its place in the unit, so ``blocks`` is
asked first).

    python3 benchmarks/tools/trace_table_mimo_v2.py --workload <cell> [--steps 2]

Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import scopes as S, trace as T  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.reference.mimo_v2 import letters, places  # noqa: E402
from benchmarks.tools import trace_table  # noqa: E402

trace_table.SCOPES = (
    "flash_swa_fwd", "flash_swa_bwd_dq", "flash_swa_bwd_dkv", "attn_sink",
    "experts", "moe_route", "moe_dispatch", "moe_combine",
    *trace_table.SCOPES, "moe")


def kinds(conf: dict) -> dict[str, list[str]]:
    """``{"F": [...], "W": [...]}``: the first name-stack component of the
    layers of each attention kind, a stack once."""
    out: dict[str, list[str]] = {"F": [], "W": []}
    for place in places(letters(conf)):
        name = place.prefix.split("/")[0]
        if name not in out[place.kind.upper()]:
            out[place.kind.upper()].append(name)
    return out


#: the accepted cell's placement (``benchmarks/configs/mimo-v2-flash-lora.json``)
KINDS = kinds(Manifest().config("mimo-v2-flash-lora"))


def main() -> None:
    trace_table.main()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args()
    manifest = Manifest()
    by_kind = kinds(manifest.config(manifest.workloads[args.workload]["config"]))
    scratch = ROOT / ".cache" / "benchmarks" / args.workload
    tr = T.load(str(scratch / "trace"))
    run = types.SimpleNamespace(trace=tr, traced=T.window(tr), scratch=scratch)
    chips = max(1, len(tr.devices))
    step = (run.traced[1] - run.traced[0]) / args.steps
    passes = (*S.PASSES, None)
    # a stacked layer's names hold the stack's AND a unit layer's: stacks first
    order = sorted(((name, kind) for kind, names in by_kind.items()
                    for name in names), key=lambda pair: not pair[0].startswith("blocks"))
    rows = [f"{kind} {part}" for kind in by_kind for part in ("attn", "rest")]
    sums = {row: dict.fromkeys(passes, 0.0) for row in rows}
    for op in (op for chip in S.step_ops(run) for op in chip):
        kind = next((kind for name, kind in order if name in op.names), None)
        if kind is not None:
            part = "attn" if "attn" in op.names else "rest"
            sums[f"{kind} {part}"][op.which_pass] += op.seconds / chips / args.steps
    print("\nby attention kind (attention with its projections | the layer's rest)")
    print(f"{'':14}" + "".join(f"{p or 'no pass':>11}" for p in passes)
          + f"{'% of step':>11}")
    for row in rows:
        total = sum(sums[row].values())
        print(f"{row:14}" + "".join(f"{sums[row][p]:11.4f}" for p in passes)
              + f"{100 * total / step:11.2f}")


if __name__ == "__main__":
    main()
