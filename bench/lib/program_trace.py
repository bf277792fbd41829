"""What the served program says about itself in a profiler trace: its host
spans (``serve.*``) nested in the harness's (``bench.*``), the
``jax.named_scope`` path of each device operation, and the time of the
decode program under each scope.

The program (``repro.serving.continuous``) opens ``serve.admit`` and
``serve.decode`` around each admission round and each fused decode
dispatch, with children for the phases: ``serve.admit.prefill``,
``.scatter``, ``.first_token``, ``.sync``; ``serve.decode.dispatch``,
``.fetch`` (the host waits for the token block), ``.settle``.  Their
arguments (``steps``, ``active``, ``rids``, ``bucket``) are event stats.
The decode step's operations carry the scopes the program exports
(``continuous.SCOPES``); what the layer scan adds, and copies XLA
inserts, carry none of them.

These are the pieces a reader of the benchmark's own trace reduction
(``trace.py``) would take over: ``spans`` and ``scope_paths`` read what
``trace.read_xplane`` leaves out; ``innermost`` and ``by_label`` put the
device's idle time down to the innermost host span around it, so it sums
to the same total idle as ``trace.reduce`` and splits that reduction's
``bench.*`` entries among the ``serve.*`` spans inside them; ``scopes``
splits the decode program's device time by scope.  ``breakdown`` puts
them together for ``bench/tools/breakdown.py``.  A program without the
spans and scopes gives None or nothing for what needs them.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq

from bench.lib import trace as trace_lib
from repro.serving.continuous import SCOPES

SERVE_PREFIX = "serve."
DECODE = "serve.decode"
FUSED = "jit__fused_impl"
UNSCOPED = "unscoped"
# the stat that holds an operation's op_name (its scope path); on the TPU
# it sits in the event's metadata, as "jit(f)/scope/op"
PATH_STAT = "tf_op"
TOP = 15


@dataclasses.dataclass
class Span:
    name: str
    start: int                     # ns
    end: int
    args: dict


def scope_of(path: str) -> str:
    """The innermost scope the program exports in an op_name path, or
    ``unscoped``."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int,
    a length-delimited field as a ``memoryview`` slice, fixed-width ones
    as raw bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_entry(buf):
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, b"")


def metadata_stats(data: bytes, plane: str) -> dict:
    """Of the XSpace ``data``, the plane named ``plane``: event name ->
    {stat name: value} of the string and reference stats its event
    metadata holds.  ``ProfileData`` shows an event's own stats only, and
    the compiler's per-operation facts sit in its metadata.

    The fields read (``xplane.proto``): XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 and .stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.name 2; XStat
    .metadata_id 1, .str_value 5, .ref_value 7."""
    for f, p in _fields(memoryview(data)):
        if f != 1:
            continue
        fields = list(_fields(p))
        if not any(k == 2 and bytes(v).decode() == plane for k, v in fields):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                key, meta = _map_entry(v)
                stat_names[key] = bytes(dict(_fields(meta)).get(
                    2, b"")).decode()
        out = {}
        for k, v in fields:
            if k != 4:
                continue
            name, stats = "", {}
            for mk, mv in _fields(_map_entry(v)[1]):
                if mk == 2:
                    name = bytes(mv).decode()
                elif mk == 5:
                    st = dict(_fields(mv))
                    sname = stat_names.get(st.get(1), "")
                    if 5 in st:
                        stats[sname] = bytes(st[5]).decode()
                    elif 7 in st:
                        stats[sname] = stat_names.get(st[7], "")
            out[name] = stats
        return out
    return {}


def spans(path: str) -> list:
    """The host's ``bench.*`` and ``serve.*`` spans in an ``.xplane.pb``,
    the program's with their arguments."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((trace_lib.SPAN_PREFIX, SERVE_PREFIX)):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)
                                    if e.name.startswith(SERVE_PREFIX)
                                    else {}))
    return out


def _device0(planes: dict):
    """The name and lines of the first device's plane, or None."""
    devs = sorted((int(p.rsplit(":", 1)[1]), p) for p in planes
                  if trace_lib.DEVICE_PLANE.match(p))
    return (devs[0][1], planes[devs[0][1]]) if devs else None


def scope_paths(path: str, planes: dict) -> dict:
    """Device 0's operation name -> its scope path, for the planes that
    ``trace.read_xplane`` read from ``path``."""
    dev0 = _device0(planes)
    if dev0 is None:
        return {}
    with open(path, "rb") as f:
        meta = metadata_stats(f.read(), dev0[0])
    return {n: meta.get(n, {}).get(PATH_STAT, "")
            for n, _, _ in dev0[1].get(trace_lib.OPS_LINE, ())}


def innermost(spans, lo: int, hi: int) -> list:
    """[lo, hi] cut into (start, end, label) pieces: each piece labelled
    with the innermost span over it (the latest to start), or
    ``trace.HOST`` where none is."""
    edges = sorted({lo, hi, *(t for s in spans for t in (s.start, s.end)
                              if lo < t < hi)})
    by_start = sorted(spans, key=lambda s: (s.start, -s.end))
    out, open_, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i].start <= a:
            s = by_start[i]
            heapq.heappush(open_, (-s.start, s.end, s.name))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        label = open_[0][2] if open_ else trace_lib.HOST
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def by_label(pieces, intervals) -> dict:
    """Length of disjoint sorted ``intervals`` under each label of the
    disjoint sorted ``pieces``."""
    got, j = {}, 0
    for a, b, label in pieces:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            n = min(b, intervals[k][1]) - max(a, intervals[k][0])
            if n > 0:
                got[label] = got.get(label, 0.0) + n
            k += 1
    return got


def scopes(ops, runs, paths: dict, lo: int, hi: int):
    """Device time of the decode program's operations in [lo, hi], control
    flow left out: by scope, and by (operation, scope).  ``ops``: (HLO
    text, start, end); ``runs``: the device's program runs as sorted
    (start, end, program).  A program's runs on one device do not
    overlap, so the run that holds an operation is the last to start
    before it."""
    starts = [s for s, _, _ in runs]
    by_scope, by_op = {}, {}
    for name, s, e in ops:
        if e <= lo or s >= hi or trace_lib.CONTROL_FLOW.match(name):
            continue
        j = bisect.bisect_right(starts, s) - 1
        if j < 0 or runs[j][1] < e or runs[j][2] != FUSED:
            continue
        d = min(e, hi) - max(s, lo)
        sc = scope_of(paths.get(name, ""))
        by_scope[sc] = by_scope.get(sc, 0.0) + d
        key = (trace_lib.op_name(name), sc)
        by_op[key] = by_op.get(key, 0.0) + d
    return by_scope, by_op


def _sorted(d: dict, scale: float) -> list:
    return [[k, v * scale] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])]


def breakdown(planes: dict, spans: list, paths: dict, slow=()) -> dict | None:
    """The window seen through the program's instrumentation; None where
    the trace holds no window or no device.  ``slow``: the window's
    slowest loop passes, (seconds, start after the window opened, ...)."""
    window = [s for s in spans if s.name == trace_lib.WINDOW]
    dev0 = _device0(planes)
    if not window or dev0 is None:
        return None
    lo, hi = min(s.start for s in window), max(s.end for s in window)
    lines = dev0[1]
    ops = [(n, s, s + d) for n, s, d in lines.get(trace_lib.OPS_LINE, ())]
    runs = sorted((s, s + d, trace_lib.program_name(n))
                  for n, s, d in lines.get(trace_lib.MODULES_LINE, ()))
    inside = [s for s in spans
              if s.name != trace_lib.WINDOW and s.end > lo and s.start < hi]
    busy = trace_lib.union(trace_lib.clip(
        [(s, e) for _, s, e in ops], lo, hi))
    idle = trace_lib.complement(busy, lo, hi)
    span_s = {}
    for s in inside:
        if s.name.startswith(SERVE_PREFIX):
            span_s[s.name] = span_s.get(s.name, 0) + (
                min(s.end, hi) - max(s.start, lo))
    decode = [s for s in inside if s.name == DECODE and s.start >= lo]
    steps = sum(int(s.args.get("steps", 0)) for s in decode)
    decode_idle = trace_lib.overlap(idle, trace_lib.union(
        [(s.start, min(s.end, hi)) for s in decode]))
    by_scope, by_op = scopes(ops, runs, paths, lo, hi)
    fused = sum(by_scope.values())
    ns = 1e-9
    stalls = []
    for dur, start, *_ in slow:
        a = lo + int(start * 1e9)
        b = a + int(dur * 1e9)
        held = by_label(innermost([s for s in inside
                                   if s.end > a and s.start < b], a, b),
                        [(a, b)])
        name, got = max(held.items(), key=lambda kv: kv[1],
                        default=("", 0.0))
        stalls.append([start, dur, name, got * ns,
                       trace_lib.overlap(busy, [(a, b)]) * ns])
    return {
        "idle_gaps": _sorted(by_label(innermost(inside, lo, hi), idle), ns),
        "span_s": {k: v * ns for k, v in sorted(span_s.items())},
        "decode_steps": steps,
        "step_gap_ms": decode_idle * ns * 1e3 / steps if steps else None,
        "scope_s": dict(_sorted(by_scope, ns)),
        "unscoped_share": (by_scope.get(UNSCOPED, 0.0) / fused * 100
                           if any(k != UNSCOPED for k in by_scope)
                           else None),
        "fused_ops": [[op, sc, v] for (op, sc), v in
                      _sorted(by_op, ns)[:TOP]],
        "slow": stalls}
