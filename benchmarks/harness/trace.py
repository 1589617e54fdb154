"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU trace
holds (looked at by hand, PERF.md section 6): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed HLO
operation, NAMED BY THE WHOLE INSTRUCTION TEXT (``%fusion.861 = bf16[...]
fusion(...)``; a Pallas kernel is one ``custom-call`` event; a ``while`` is
one event spanning its body's events) and whose line ``XLA Modules`` has one
event per executed program (``jit__train_step(<hash>)``); host threads are
lines of the ``/host:CPU`` plane, and the benchmark's own ``TraceAnnotation``
spans land there (line ``python3``) on the same clock.

All times are seconds.  Intervals are ``(start, end)`` pairs.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """``devices[n]``: op events of chip n; ``modules[n]``: its program
    events; ``host``: annotation events of every host thread."""
    devices: dict[int, list[Event]]
    modules: dict[int, list[Event]]
    host: list[Event]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def from_profile_data(pd, host_names: Iterable[str] = ()) -> Trace:
    """``host_names``: the annotation names worth keeping from the host
    planes (everything else there is the profiler's own Python tracing)."""
    keep = set(host_names)
    devices: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            n = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(n, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(n, []).extend(_events(line))
        elif plane.name.startswith("/host:") and keep:
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.name in keep)
    return Trace(devices, modules, host)


def load(trace_dir_or_file: str, host_names: Iterable[str] = ()) -> Trace:
    from jax.profiler import ProfileData

    path = trace_dir_or_file
    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile_data(ProfileData.from_file(path), host_names)


# ---- interval arithmetic ---------------------------------------------------

def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of union(a) not covered by union(b)."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _iv(events: Iterable[Event]):
    """Intervals of the leaf operations: a container's span says nothing
    about whether an operation was running."""
    return [(e.start, e.end) for e in events if not is_container(e.name)]


# ---- what the benchmark reads ----------------------------------------------

def window(trace: Trace) -> tuple[float, float]:
    """First leaf-op start to last leaf-op end over all chips."""
    spans = [iv for evs in trace.devices.values() for iv in _iv(evs)]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which a leaf operation ran, averaged over
    chips."""
    per = [total(clip(union(_iv(evs)), lo, hi))
           for evs in trace.devices.values()]
    return sum(per) / len(per)


def kernel_events(trace: Trace, pattern: str, module: str | None = None,
                  device: int | None = None) -> list[Event]:
    """Device-op events whose name matches ``pattern``; with ``module``, only
    those that ran inside a program whose name matches it."""
    rx = re.compile(pattern)
    out = []
    for n, evs in trace.devices.items():
        if device is not None and n != device:
            continue
        hits = [e for e in evs if rx.search(e.name)]
        if module is not None:
            mrx = re.compile(module)
            spans = [(m.start, m.end) for m in trace.modules.get(n, [])
                     if mrx.search(m.name)]
            hits = [e for e in hits
                    if any(s <= e.start and e.end <= t for s, t in spans)]
        out.extend(hits)
    return out


def kernel_seconds(trace: Trace, pattern: str, module: str | None = None) -> float:
    """Summed device time of the matching kernels, averaged over chips."""
    evs = kernel_events(trace, pattern, module)
    return sum(e.seconds for e in evs) / max(1, len(trace.devices))


CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
_HLO = re.compile(r"^%?([^ =]+) = (\(?[A-Za-z0-9]+\[[^\]]*\])")


def is_container(name: str) -> bool:
    """``while`` / ``conditional`` / ``call`` events span the events of their
    bodies (a scanned layer stack is one ``while``), waits between them
    included: they are left out of busy time and of every sum."""
    return bool(CONTAINER.match(name))


def label(name: str) -> str:
    """An event's name is the whole HLO instruction; keep its result name and
    first result type: ``fusion.861 bf16[8,2048,14336]``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else name[:96]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The device operations that took most time: [label, seconds], summed
    over calls (a scanned layer's op keeps one name over the layers) and
    averaged over chips; container ops are left out."""
    sums: dict[str, float] = {}
    for evs in trace.devices.values():
        for e in evs:
            if not is_container(e.name):
                key = label(e.name)
                sums[key] = sums.get(key, 0.0) + e.seconds
    k = max(1, len(trace.devices))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10,
              device: int | None = None) -> list[list]:
    """The idle time of one chip inside [lo, hi], by what the host was doing:
    each gap between device ops is charged to the innermost benchmark
    annotation open when the gap began (``unannotated`` if none); returns
    the n largest sums as [name, seconds]."""
    if device is None:
        device = min(trace.devices)
    busy = clip(union(_iv(trace.devices[device])), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    sums: dict[str, float] = {}
    for s, e in gaps:
        open_ = [h for h in trace.host if h.start <= s < h.end]
        name = (min(open_, key=lambda h: h.end - h.start).name
                if open_ else "unannotated")
        sums[name] = sums.get(name, 0.0) + (e - s)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def attach(run, out: dict, trace_dir: str, host_names: Iterable[str]) -> None:
    """Read the run's trace and put what the last line needs into ``out``:
    ``device.busy_s`` / ``window_s`` and the ``breakdown``.  A trace with no
    device operation is an error on a chip (the run then prints no result);
    off the chip (the tests' CPU rehearsal) the per-layer readers simply
    find nothing to read."""
    tr = load(trace_dir, host_names)
    if not tr.devices:
        if run.device_kind.lower().startswith("tpu"):
            raise ValueError("the trace holds no device operation")
        return
    lo, hi = window(tr)
    run.trace, run.traced = tr, (lo, hi)
    out["device"].update(busy_s=busy_seconds(tr, lo, hi), window_s=hi - lo)
    out["breakdown"] = {"device_ops": top_ops(tr),
                        "idle_gaps": idle_gaps(tr, lo, hi)}
