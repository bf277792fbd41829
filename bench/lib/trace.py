"""From a profiler trace to device busy time, each layer's device time, and
the device's idle gaps, each put down to what the host was doing.

The harness marks the host side with ``jax.profiler.TraceAnnotation`` spans:
``bench.window`` around the measured window, and inside it one span around
each call into the system (``bench.admit``, ``bench.step``)
and ``bench.wait`` while it sleeps until the next arrival.  Host time in the
window outside those is the loop's own bookkeeping, ``host``.

A layer's device time is the device's busy time that overlaps the harness's
spans of that layer.  Device planes are those named ``/device:TPU:<n>``; on
each, the line ``XLA Ops`` holds one event per operation run and
``XLA Modules`` one per program run, named after the jitted function.
Busy time is the union of the operations' intervals.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
HOST = "host"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# control flow whose events span the operations nested in them
CONTROL_FLOW = re.compile(r"^%?(while|conditional|call)[.\s]")
TOP = 10


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float                  # mean over devices
    devices: int
    span_s: dict                   # span name -> host seconds in the window
    span_busy_s: dict              # span name -> device-0 busy seconds in it
    module_s: dict                 # program name -> device-0 seconds
    device_ops: list               # [[name, seconds]], top by time, device 0,
                                   # control flow left out (it spans the
                                   # operations nested in it)
    idle_gaps: list                # [[span name, seconds]] of device-0 idle


def union(intervals) -> list:
    """Merge (start, end) pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def complement(intervals, lo, hi) -> list:
    """The parts of [lo, hi] that disjoint sorted ``intervals`` leave."""
    out, t = [], lo
    for s, e in intervals:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(xs, ys) -> float:
    """Length shared by two disjoint sorted interval lists."""
    i = j = 0
    got = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            got += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return got


def program_name(event_name: str) -> str:
    """``jit__fused_impl(123)`` -> ``jit__fused_impl``."""
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``fusion.3
    bf16[8,128]``: the operation and the shape it writes."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + " " + shape).strip()


def reduce(planes: dict) -> Trace | None:
    """``planes``: plane name -> line name -> [(name, start_ns, dur_ns)].
    Host spans may sit on any line of any plane that is not a device.
    None where the trace holds no window or no device."""
    spans, devices = {}, []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            devices.append((int(pname.rsplit(":", 1)[1]), lines))
            continue
        for evs in lines.values():
            for name, start, dur in evs:
                if name.startswith(SPAN_PREFIX):
                    spans.setdefault(name, []).append((start, start + dur))
    if WINDOW not in spans or not devices:
        return None
    lo = min(s for s, _ in spans[WINDOW])
    hi = max(e for _, e in spans[WINDOW])
    devices.sort()
    busy = []
    for _, lines in devices:
        ops = [(s, s + d) for _, s, d in lines.get(OPS_LINE, ())]
        busy.append(union(clip(ops, lo, hi)))
    busy0 = busy[0]
    lines0 = devices[0][1]
    host = {k: union(clip(v, lo, hi)) for k, v in spans.items() if k != WINDOW}
    module_s, ops_s = {}, {}
    for name, s, d in lines0.get(MODULES_LINE, ()):
        got = total(clip([(s, s + d)], lo, hi))
        if got:
            key = program_name(name)
            module_s[key] = module_s.get(key, 0.0) + got
    for name, s, d in lines0.get(OPS_LINE, ()):
        got = clip([(s, s + d)], lo, hi)
        if not got:
            continue
        if not CONTROL_FLOW.match(name):
            key = op_name(name)
            ops_s[key] = ops_s.get(key, 0.0) + total(got)
    idle = complement(busy0, lo, hi)
    covered = union([iv for v in host.values() for iv in v])
    gaps = {k: overlap(idle, v) for k, v in host.items()}
    gaps[HOST] = total(idle) - overlap(idle, covered)
    ns = 1e-9
    top = sorted(ops_s.items(), key=lambda kv: -kv[1])[:TOP]
    return Trace(
        window_s=(hi - lo) * ns,
        busy_s=sum(total(b) for b in busy) / len(busy) * ns,
        devices=len(busy),
        span_s={k: total(v) * ns for k, v in host.items()},
        span_busy_s={k: overlap(busy0, v) * ns for k, v in host.items()},
        module_s={k: v * ns for k, v in module_s.items()},
        device_ops=[[k, v * ns] for k, v in top],
        idle_gaps=[[k, v * ns] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])][:TOP])


def read_xplane(path: str) -> dict:
    """An ``.xplane.pb`` as plane -> line -> [(name, start_ns, dur_ns)],
    keeping the host's ``bench.*`` spans and the devices' op and program
    lines."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if dev or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


class Profiler:
    """The JAX profiler over one window, writing into ``directory``."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def read(self) -> Trace | None:
        paths = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        try:
            return reduce(read_xplane(paths[-1])) if paths else None
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
