"""The readings a cell's limits are set from, in ONE process after one
set-up: the numbers compared, for the sound program on many seeds and for
the lower-precision control on a few.

    python3 benchmarks/tools/limit_readings.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 [--seconds 12] --out <file>

Training cells need no measured window: each seed is driven through its
first steps only.  Serving cells run a short window at the cell's own load.
The control is the plain reference computed in scaled float8 (``to_fp8``:
e4m3 operands, e5m2 cotangents), the step below the bf16 the configurations
state; for serving it does not decode — at each position of the program's
own prompts and tokens the gap of the token float8 puts first is read.  Not
part of a benchmark run.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as runner  # noqa: E402
from benchmarks.harness import compare, result  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.recorder import Recorder  # noqa: E402
from benchmarks.reference import model as ref_model  # noqa: E402


def gaps(prog: dict, ref: dict) -> dict:
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "first_grad_norm_gap": compare.worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "param_change_norm_gap": compare.worst_leaf_gap(prog["delta_norms"], ref["delta_norms"]),
    }


def serve_numbers(g: dict) -> dict:
    return {"served_token_mean_logit_gap": g["mean"],
            "served_token_widest_logit_gap": g["widest"]}


def train_readings(run, seeds, control_seeds):
    from benchmarks.harness.drivers import train

    trainer = train.build_trainer(run)
    reference = run.manifest.reference(run.conf).reference_numbers
    rows = []
    for seed in seeds:
        state, _step, feed, prog, tokens, step_s = train.first_steps(run, trainer, seed)
        feed.close()
        del state, _step
        t = time.perf_counter()
        ref = reference(run.conf, run.workload, seed, tokens)
        row = {"seed": seed, "program": gaps(prog, ref),
               "reference_s": time.perf_counter() - t, "step_s": step_s}
        if seed in control_seeds:
            t = time.perf_counter()
            control = reference(run.conf, run.workload, seed, tokens,
                                q=ref_model.to_fp8, precision="default")
            row["control_fp8"] = gaps(control, ref)
            row["control_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def serve_readings(run, seeds, control_seeds, seconds):
    from benchmarks.harness.drivers import serve

    rows = []
    for seed in seeds:
        run.seed, run.recorder = seed, Recorder()
        engine = serve.build_engine(run)
        sent, marks = serve.offer_session(run, engine, run.workload["traffic"], seconds)
        engine._cache = engine.variables = None
        del engine
        samples = serve.sample_finished(run, sent)
        t = time.perf_counter()
        g = serve.served_gaps(run.conf, seed, samples)
        row = {"seed": seed, "program": serve_numbers(g),
               "tokens": g["tokens"], "requests": len(samples),
               "flipped_share": g["flipped_share"],
               "reference_s": time.perf_counter() - t}
        if seed in control_seeds:
            t = time.perf_counter()
            g = serve.served_gaps(run.conf, seed, samples,
                                  control_q=ref_model.to_fp8)
            row["control_fp8"] = serve_numbers(g)
            row["control_flipped_share"] = g["flipped_share"]
            row["control_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    manifest = Manifest()
    entry = manifest.workloads[args.workload]
    from finetune_controller_tpu.platform import enable_compile_cache

    enable_compile_cache()
    device = result.device_report(entry["chips"])
    run = runner.Run(
        manifest=manifest, cell=args.workload,
        workload=manifest.workload(args.workload),
        conf=manifest.config(entry["config"]), seed=seeds[0],
        seconds=args.seconds, trace_on=False, chips=entry["chips"], t0=_T0,
        recorder=Recorder(), scratch=ROOT / ".cache" / "benchmarks" / "limits",
        device_kind=device["kind"])
    if run.workload["driver"] == "train":
        rows = train_readings(run, seeds, control)
    else:
        rows = serve_readings(run, seeds, control, args.seconds)
    summary = {}
    for key in rows[0]["program"]:
        summary[key] = {
            "program_largest": max(r["program"][key] for r in rows),
            "control_smallest": min((r["control_fp8"][key] for r in rows
                                     if "control_fp8" in r), default=None),
        }
    out = {"cell": args.workload, "device": device, "summary": summary,
           "limits_in_cell_file_when_read": run.workload["limits"], "readings": rows}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
