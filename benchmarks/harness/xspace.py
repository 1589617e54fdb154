"""The parts of a profiler trace that ``jax.profiler.ProfileData`` does not
hand out, read from the serialized ``XSpace`` itself.

On a TPU the name stack JAX gives an operation (``jit(_train_step)/jvp(
LlamaForCausalLM)/while/body/closed_call/blocks/block/attn/q_proj/
dequant_int4/convert_element_type``) is neither in an ``XLA Ops`` event's
name (the instruction text, printed without its ``metadata={...}``) nor in
the event's stats: it is the stat ``tf_op`` of the event's METADATA (looked at
by hand, PERF.md section 6, PR 24), and ``ProfileData`` shows an event's own
stats only.  So this file walks the protobuf wire format — the few fields of
``xplane.proto`` it needs, nothing else — and returns plain tuples.

Times are seconds on the trace's clock, the one ``harness/trace.py`` reports
(``line.timestamp_ns`` + ``event.offset_ps``).
"""

from __future__ import annotations

import dataclasses
import os
import struct

from benchmarks.harness import trace as T

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS, _EVENT_STATS = 1, 2, 3, 4
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STATMETA_NAME = 1, 2
_STAT_VALUES = {2: "double", 3: "uint64", 4: "int64", 5: "str", 6: "bytes",
                7: "ref"}
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview):
    """(field number, value) pairs of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield field, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: dict) -> tuple[str, object]:
    name, value = "", None
    for f, v in _fields(buf):
        if f == _STAT_META_ID:
            name = stat_names.get(v, str(v))
        elif f in _STAT_VALUES:
            kind = _STAT_VALUES[f]
            if kind == "double":
                v = struct.unpack("<d", struct.pack("<Q", v))[0]
            elif kind == "int64" and v >= 1 << 63:
                v -= 1 << 64
            elif kind == "str":
                v = _text(v)
            elif kind == "ref":
                v = stat_names.get(v, str(v))
            elif kind == "bytes":
                v = bytes(v)
            value = v
    return name, value


@dataclasses.dataclass(frozen=True)
class Event(T.Event):
    """``name`` is the event metadata's; ``stats`` the metadata's stats
    overlaid by the event's own."""
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict[str, list[Event]]   # events of same-named lines are joined


def _plane(buf) -> Plane:
    name, lines, metas, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == _PLANE_NAME:
            name = _text(v)
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_META:
            metas.append(v)
        elif f == _PLANE_STAT_META:
            key, label = 0, ""
            for mf, mv in _fields(v):
                if mf == _MAP_KEY:
                    key = mv
                elif mf == _MAP_VALUE:
                    for sf, sv in _fields(mv):
                        if sf == _STATMETA_NAME:
                            label = _text(sv)
            stat_names[key] = label
    meta: dict[int, tuple[str, dict]] = {}
    for entry in metas:
        key, label, stats = 0, "", {}
        for mf, mv in _fields(entry):
            if mf == _MAP_KEY:
                key = mv
            elif mf == _MAP_VALUE:
                for ef, ev in _fields(mv):
                    if ef == _META_NAME:
                        label = _text(ev)
                    elif ef == _META_STATS:
                        k, val = _stat(ev, stat_names)
                        stats[k] = val
        meta[key] = (label, stats)
    out: dict[str, list[Event]] = {}
    for line in lines:
        label, t0_ns, events = "", 0, []
        for f, v in _fields(line):
            if f == _LINE_NAME:
                label = _text(v)
            elif f == _LINE_TIMESTAMP_NS:
                t0_ns = v
            elif f == _LINE_EVENTS:
                events.append(v)
        row = out.setdefault(label, [])
        for ev in events:
            mid = offset_ps = duration_ps = 0
            own = {}
            for f, v in _fields(ev):
                if f == _EVENT_META_ID:
                    mid = v
                elif f == _EVENT_OFFSET_PS:
                    offset_ps = v
                elif f == _EVENT_DURATION_PS:
                    duration_ps = v
                elif f == _EVENT_STATS:
                    k, val = _stat(v, stat_names)
                    own[k] = val
            label_, stats = meta.get(mid, ("", {}))
            start = t0_ns * 1e-9 + offset_ps * 1e-12
            row.append(Event(label_, start, start + duration_ps * 1e-12,
                             {**stats, **own} if own else stats))
    return Plane(name, out)


def parse(serialized: bytes) -> list[Plane]:
    return [_plane(v) for f, v in _fields(memoryview(serialized))
            if f == _SPACE_PLANES]


def load(trace_dir_or_file: str) -> list[Plane]:
    path = str(trace_dir_or_file)
    if os.path.isdir(path):
        path = T.find_xplane(path)
    with open(path, "rb") as f:
        return parse(f.read())


def from_text_proto(text: str) -> list[Plane]:
    """A recorded fixture (the tests'): text proto to planes."""
    from jax.profiler import ProfileData

    return parse(ProfileData.text_proto_to_serialized_xspace(text))


def run_planes(run) -> list[Plane] | None:
    """The planes of this run's trace, read once; ``None`` where the run
    took no trace."""
    if not hasattr(run, "_xspace_planes"):
        try:
            run._xspace_planes = load(str(run.scratch / "trace"))
        except FileNotFoundError:
            run._xspace_planes = None
    return run._xspace_planes
