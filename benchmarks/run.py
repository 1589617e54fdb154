"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell in THIS process, which holds the chip(s), and prints one JSON
object as the last line of its standard output.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` a profiler
trace is taken inside the window and the metrics are its per-layer metrics.
Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import counts, result  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.recorder import Recorder  # noqa: E402


@dataclasses.dataclass
class Run:
    """What a driver gets, fills in, and a reducer reads."""
    manifest: Manifest
    cell: str
    workload: dict
    conf: dict
    seed: int
    seconds: float
    trace_on: bool
    chips: int
    t0: float
    recorder: Recorder
    scratch: Path
    device_kind: str = ""
    # filled by the driver
    window_s: float = 0.0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    trace: Any = None                 # harness.trace.Trace
    traced: tuple = (0.0, 0.0)        # the traced window on the trace's clock
    notes: dict = dataclasses.field(default_factory=dict)
    stages: list = dataclasses.field(default_factory=list)

    @property
    def peaks(self) -> dict:
        return counts.peaks_for(self.device_kind)

    def since_start(self) -> float:
        return time.perf_counter() - self.t0

    def stage(self, name: str) -> None:
        """Mark the end of a set-up stage (seconds since process start)."""
        self.stages.append((name, self.since_start()))

    def setup_split(self) -> str:
        parts, last = [], 0.0
        for name, t in self.stages:
            parts.append(f"{name} {t - last:.2f} s")
            last = t
        return "setup split: " + ", ".join(parts)


def main(argv=None, manifest_path=None, allow_cpu: bool = False) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = Manifest(manifest_path)
    if args.workload not in manifest.workloads:
        sys.exit(f"benchmark: unknown cell {args.workload!r}")
    entry = manifest.workloads[args.workload]
    workload = manifest.workload(args.workload)
    conf = manifest.config(entry["config"])

    # the program's helper decides where compiled programs are kept:
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.cache/xla — a
    # fixed path inside the checkout either way it is ours to choose
    from finetune_controller_tpu.platform import enable_compile_cache

    enable_compile_cache()
    if allow_cpu:
        import jax

        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind,
                  "count": entry["chips"]}
    else:
        device = result.device_report(entry["chips"])

    scratch = ROOT / ".cache" / "benchmarks" / args.workload
    scratch.mkdir(parents=True, exist_ok=True)
    run = Run(manifest=manifest, cell=args.workload, workload=workload,
              conf=conf, seed=args.seed, seconds=args.seconds,
              trace_on=bool(args.trace), chips=entry["chips"], t0=_T0,
              recorder=Recorder(annotate=bool(args.trace)), scratch=scratch,
              device_kind=device["kind"])
    run.stage("python start, imports, devices")
    out = manifest.driver(workload["driver"])(run)

    device.update(out["device"])
    if run.trace_on:
        names = manifest.cell_per_layer(args.workload)
        metrics, units = {}, {}
        for name in names:
            spec = manifest.layer_metric(name)
            value = manifest.reducer(spec["reducer"])(run, **spec.get("args", {}))
            if value is not None:
                metrics[name], units[name] = value, spec["unit"]
    else:
        names = manifest.cell_end_to_end(args.workload)
        metrics = {n: run.end_to_end[n] for n in names}
        units = {n: manifest.end_to_end[n]["unit"] for n in names}
    line = dict(correct=out["correct"], attempted=out["attempted"],
                failed=out["failed"], metrics=metrics, units=units,
                device=device, breakdown=out.get("breakdown"),
                compared=out.get("compared"))
    result.emit(**line)
    return line


if __name__ == "__main__":
    main()
