"""Seconds of the PROGRAM's own start-up log
(``finetune_controller_tpu/obs/trace.py::StartupLog``: imports, the backend,
``Trainer()``, every program compiled or loaded, the first step — closed when
the first ``Trainer.step`` returned), read in this process: over the log's
spans called any of ``spans`` and carrying the attributes ``where``, the sum
of their seconds — or of their attributes ``fields`` — less their attributes
``minus``.  None where the program keeps no such log, or kept nothing in it."""

import sys


def _spans():
    try:
        from finetune_controller_tpu.obs import trace
    except ImportError:
        return []
    log = getattr(trace, "STARTUP", None)
    return list(log.spans) if log is not None else []


def reduce(run, spans=None, fields=None, minus=(), where=None):
    log = _spans()
    if not log:
        return None
    found = [s for s in log if s["name"] in (spans or ()) and all(
        s["attributes"].get(k) == v for k, v in (where or {}).items())]
    seconds = 0.0
    for s in found:
        a = s["attributes"]
        seconds += (sum(a.get(f) or 0.0 for f in fields) if fields
                    else (s["end_ns"] - s["start_ns"]) / 1e9)
        seconds -= sum(a.get(f) or 0.0 for f in minus)
    setup_s = run.end_to_end.get("setup_s")
    if setup_s is not None and seconds > setup_s:
        sys.exit(f"benchmark: start-up spans {spans} read {seconds} s of a "
                 f"set-up of {setup_s} s — seconds counted twice")
    programs = [s for s in found if s["name"].startswith("compile")]
    if programs:
        longest = sorted(
            (s for s in programs if "fun_name" in s["attributes"]),
            key=lambda s: s["start_ns"] - s["end_ns"])[:5]
        count = sum(s["attributes"].get("count", 1) for s in programs)
        print(f"start-up: {count} program(s) {where or ''}, the longest: "
              + ", ".join(
                  f"{s['attributes']['fun_name']} "
                  f"{(s['end_ns'] - s['start_ns']) / 1e9:.2f} s "
                  f"({s['attributes']['cache']})" for s in longest),
              file=sys.stderr, flush=True)
    return max(seconds, 0.0)
