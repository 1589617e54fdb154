"""The last line of a run: one JSON object, every number with all its digits."""

from __future__ import annotations

import json
import math
import sys


def device_report(chips: int) -> dict:
    """The devices as JAX reports them.  Fails (non-zero exit, no result)
    when no TPU is found or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: no TPU found (platform {devices[0].platform!r}); "
                 "a device metric is never measured on another platform")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips, JAX found "
                 f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def allocator_peak_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest of the first ``chips`` devices
    (0 where the backend reports none)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         units: dict, device: dict, breakdown: dict | None = None,
         compared: dict | None = None) -> None:
    """``compared``: each number that decided ``correct`` beside its limit;
    printed as the last lines of standard error, and last in the line."""
    for name, value in metrics.items():
        if not isinstance(value, (int, float)) or not math.isfinite(value):
            sys.exit(f"benchmark: metric {name} is not a finite number: {value!r}")
        if (name.endswith("_roofline") or "mfu" in name) and value > 105.0:
            sys.exit(f"benchmark: {name} reads {value} % — above any peak: "
                     "the operations or bytes are counted too high, or the "
                     "time leaves out part of the work")
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {n: {"value": float(v), "unit": units[n]}
                    for n, v in metrics.items()},
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if compared:
        line["compared"] = compared
        for name, row in compared.items():
            print(f"compared {name}: {row['value']:.6g} (limit {row['limit']:g})",
                  file=sys.stderr)
        sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
