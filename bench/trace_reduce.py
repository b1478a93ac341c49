"""Reduce a profiler trace (``*.xplane.pb``) to the benchmark's
device numbers.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``)
whose ``XLA Ops`` line has one event per operation run on the
TensorCore, and a host plane (``/host:CPU``) with a line per thread;
the benchmark's thread holds its ``bench.*`` annotations and, with
the profiler's Python tracer on, its Python calls.  Host and device
events share one clock.  The events are read with
``jax.profiler.ProfileData``; the HLO metadata of each operation (its
``tf_op``: the jit name and the ``jax.named_scope`` path, such as
``jit(_run)/while/body/bfs.expand/...``) sits in the plane's event
metadata, which that reader does not expose, so `_op_paths` decodes
it from the protobuf's wire format.

The traced window runs from the first to the end of the last
``bench.*`` annotation.  Busy time is the union of the operations'
intervals inside it, averaged over the chips.  An operation that
encloses later ones on its line (a ``while`` or ``conditional``
around its body) counts toward busy time only; a scope's device time
is the summed duration of the other operations whose path has the
scope as a component, and the top operations are ranked by the same
sums.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
#: the named scopes of the engine's layer loop (`core.engine`)
SCOPES = ("bfs.expand", "bfs.measure_decide", "bfs.stats")
TOP = 10


# -- the protobuf wire format, as far as XSpace's metadata needs it -----

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """Yield ``(field number, value)``; a length-delimited value is the
    bytes it holds, any other the integer (fixed widths unparsed)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
# key 1, value 2), .stat_metadata = 5; XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7.

def _op_paths(raw: bytes) -> dict[str, dict[str, str]]:
    """``{device plane name: {op event name: tf_op path}}``."""
    out = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = meta.get(2, b"").decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        paths = {}
        for ev in events:
            ev_name, path = "", ""
            for f, v in _fields(ev):
                if f == 2:
                    ev_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        value = stat.get(5)
                        if value is None and 7 in stat:
                            value = stat_names.get(stat[7], "").encode()
                        path = (value or b"").decode()
            paths[ev_name] = path
        out[name] = paths
    return out


# -- intervals --------------------------------------------------------------

def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for s, e in sorted(intervals):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


def gaps(intervals, lo: float, hi: float):
    """``(start, end)`` of every stretch of ``[lo, hi]`` that no
    interval covers."""
    out, reach = [], lo
    for s, e in sorted(intervals):
        if s > reach:
            out.append((reach, min(s, hi)))
        reach = max(reach, e)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(s, e) for s, e in out if e > s]


def has_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


@dataclasses.dataclass
class Summary:
    """Device numbers of one traced window (seconds)."""
    window_s: float
    busy_s: float
    scope_s: dict
    top_ops: list       # [[path or op name, seconds], ...]
    idle_gaps: list     # [[host phase, seconds], ...]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.idle_gaps}


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    op_paths = _op_paths(raw)
    data = ProfileData.from_serialized_xspace(raw)
    host, marks = [], []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            mine = [e for e in events if e[2].startswith(ANNOTATION_PREFIX)]
            if mine:            # the benchmark's own thread
                host, marks = events, mine
    if not marks:
        raise ValueError(f"{path}: no {ANNOTATION_PREFIX}* annotation")
    lo = min(m[0] for m in marks)
    hi = max(m[1] for m in marks)
    busy, chips = 0.0, 0
    scope_ns = {s: 0.0 for s in SCOPES}
    per_op: dict[str, float] = {}
    idle: dict[str, float] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        paths = op_paths.get(plane.name, {})
        events = sorted(((e.start_ns, e.end_ns, e.name)
                         for line in plane.lines if line.name == OPS_LINE
                         for e in line.events), key=lambda x: (x[0], -x[1]))
        ops = []
        for k, (start, end, name) in enumerate(events):
            s, t = max(start, lo), min(end, hi)
            if t <= s:
                continue
            ops.append((s, t))
            if k + 1 < len(events) and events[k + 1][0] < end:
                continue        # a loop or branch around later ops
            p = paths.get(name, "")
            key = p or name.split(" = ")[0]
            per_op[key] = per_op.get(key, 0.0) + (t - s)
            for sc in SCOPES:
                if has_scope(p, sc):
                    scope_ns[sc] += t - s
        chips += 1
        busy += union_length(ops)
        for s, t in gaps(ops, lo, hi):
            name = _phase(marks, host, (s + t) / 2)
            idle[name] = idle.get(name, 0.0) + (t - s)
    if not chips:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    ns = 1e-9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps_top = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * ns, busy_s=busy / chips * ns,
        scope_s={k: v / chips * ns for k, v in scope_ns.items()},
        top_ops=[[k, v / chips * ns] for k, v in top],
        idle_gaps=[[k, v / chips * ns] for k, v in gaps_top])


def _innermost(events, t: float):
    inside = [e for e in events if e[0] <= t <= e[1]]
    return min(inside, key=lambda e: e[1] - e[0])[2] if inside else None


def _phase(marks, host, t: float) -> str:
    """What the host was doing at time ``t``: the innermost ``bench.*``
    annotation and the innermost host event (with the profiler's
    Python tracer on, the Python function) around it."""
    mark = _innermost(marks, t) or "outside bench phases"
    inner = _innermost(host, t)
    return mark if inner in (None, mark) else f"{mark} > {inner}"


def reduce_dir(trace_dir: str) -> Summary:
    """Reduce the one ``*.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"{trace_dir}: expected one xplane.pb, found "
                         f"{len(files)}")
    return reduce_file(files[0])
