"""Find a serving cell's knee once, on the chip: several fixed rates in one
process after one set-up.

    python3 benchmarks/tools/knee_sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 2,3,4,5,6 --out <file.json>

The knee is the highest rate with no refusal and a queue no deeper at the
end of the window than at its middle (three waiting requests or fewer are arrivals, not a backlog).  The readings go to ``--out``; the
benchmark PR that defines the cell writes 0.8 x the knee into the cell's
file and keeps the readings beside it.  Not part of a benchmark run.
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
from benchmarks.harness import result, stats  # noqa: E402
from benchmarks.harness.drivers import serve  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.recorder import Recorder  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    manifest = Manifest()
    entry = manifest.workloads[args.workload]
    from finetune_controller_tpu.platform import enable_compile_cache

    enable_compile_cache()
    device = result.device_report(entry["chips"])
    run = runner.Run(
        manifest=manifest, cell=args.workload,
        workload=manifest.workload(args.workload),
        conf=manifest.config(entry["config"]), seed=args.seed,
        seconds=args.seconds, trace_on=False, chips=entry["chips"], t0=_T0,
        recorder=Recorder(), scratch=ROOT / ".cache" / "benchmarks" / "sweep",
        device_kind=device["kind"])
    engine = serve.build_engine(run)
    readings = []
    for rate in [float(r) for r in args.rates.split(",")]:
        run.recorder = Recorder()
        traffic = dict(run.workload["traffic"], rate_rps=rate)
        sent, marks = serve.offer_session(run, engine, traffic, args.seconds)
        win = [s for s in sent if s.arrival.in_window]
        ok = [s for s in win if s.task.done() and not s.task.cancelled()
              and s.task.exception() is None]
        ttft = [s.task.result().admitted_at - s.due for s in ok]
        steps = run.recorder.series.get("step", [])
        row = {
            "rate_rps": rate, "due": len(win), "finished": len(ok),
            "refused": marks["rejected"], "queue_mid": marks.get("queue_mid"),
            "queue_end": marks["queue1"],
            "tokens_per_s": (marks["tokens1"] - marks["tokens0"]) / marks["window_s"],
            "ttft_median_ms": 1e3 * stats.median(ttft) if ttft else None,
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90) if ttft else None,
            "decode_step_median_ms": 1e3 * stats.median(steps) if steps else None,
            "lanes_busy_mean": stats.mean(run.recorder.series.get("lanes_busy", [0])),
            # a request or two just arrived is not a backlog
            "sustained": marks["rejected"] == 0 and len(ok) == len(win)
            and marks["queue1"] <= max(marks.get("queue_mid") or 0, 3),
        }
        print(json.dumps(row), flush=True)
        readings.append(row)
    knee = max((r["rate_rps"] for r in readings if r["sustained"]), default=None)
    out = {"cell": args.workload, "seed": args.seed, "seconds": args.seconds,
           "device": device, "knee_rps": knee, "readings": readings}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_rps": knee}))


if __name__ == "__main__":
    main()
