"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` does everything but look for the chip, so the tests drive it
on the CPU with small configurations and with the timed path broken.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time

from bench.lib import check, manifest, model, serve, traffic, weights
from bench.lib import trace as trace_lib
from bench.lib.peaks import peaks


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    cell: manifest.Cell
    w: model.Widths
    window: serve.Window
    trace: trace_lib.Trace | None
    peaks: dict
    chips: int
    setup_s: float


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip, where the backend reports
    it (0 where it does not, as on the CPU)."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = int(max(m.get("peak_bytes_in_use", 0) for m in stats))
    log(f"device memory: peak {peak} bytes in use of "
        f"{[m.get('bytes_limit') for m in stats]}")
    return peak


def _phase(name: str, since: float) -> float:
    now = time.perf_counter()
    log(f"set-up: {name} {now - since} s")
    return now


def _continuous(cell, w, seed, seconds, span, profiler, counter):
    mix = cell.mix
    t = time.perf_counter()
    params = weights.build(seed, w)
    t = _phase("weights built on the device", t)
    srv = serve.make_server(model.program_config(cell.config_name,
                                                 cell.config), params, mix)
    del params
    t = _phase("server constructed (cache allocated)", t)
    serve.warm_up(srv, mix, w.vocab)
    _phase("shapes warmed up", t)
    before = dict(srv.compile_stats())
    reqs = traffic.requests(mix, w.vocab, seconds, seed)
    win = serve.serve(srv, reqs, mix, seconds, span, profiler, counter)
    log(f"compile_stats before the window {before}, after "
        f"{srv.compile_stats()}; compilations inside the window "
        f"{win.compiles}")
    log(f"admissions in the window {len(win.admissions)}, decode steps "
        f"{len(win.steps)}, tokens {win.tokens} in {win.seconds} s; "
        f"generator lateness at most {win.lateness_s} s")
    for dur, start, a, s in win.slow:
        log(f"slow loop pass at {start} s: {dur} s, of it admit {a} s, "
            f"step {s} s, the rest {dur - a - s} s")
    served = {r.rid: check.Served(r.prompt, srv.out[r.rid],
                                  win.logs[r.rid].slot)
              for r in reqs if win.logs[r.rid].done}
    return srv, win, served, mix["server"]["max_seq"]


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             devices, started: float, scratch: str,
             control: bool = False) -> dict:
    """The result line of one run, with ``checks`` last.  ``started``: the
    ``perf_counter`` at process start; ``scratch``: where a trace goes.
    ``control`` also puts the control's tokens at the same positions
    through the same verdict, under ``control`` (``bench/tools/control.py``;
    the benchmark's own runs never do)."""
    w = model.widths(cell.config)
    kind = devices[0].device_kind
    span = serve.spans(trace)
    counter = serve.CompileCounter()
    profiler = trace_lib.Profiler(scratch) if trace else None
    log(f"set-up: process start to the cell's first weight "
        f"{time.perf_counter() - started} s (Python, JAX and the chip)")
    system, win, served, pad_to = _continuous(
        cell, w, seed, seconds, span, profiler, counter)
    setup_s = win.opened - started
    log(f"set-up: {setup_s} s from process start to window open")
    memory = peak_bytes(devices)
    del system
    gc.collect()
    tr = profiler.read() if profiler else None

    t = time.perf_counter()
    picked = check.sample(served, cell.mix["check"]["requests"], seed)
    ids_ok = all(0 <= int(x) < w.vocab for s in served.values()
                 for x in s.tokens)
    read = (check.compare(check.reference(cell.config["reference"]), seed,
                           w, picked, pad_to, control) if picked else {})
    correct, checks = check.verdict(read, ids_ok, cell.limits)
    log(f"reference check in {time.perf_counter() - t} s: {read}")

    run = Run(cell=cell, w=w, window=win, trace=tr,
              peaks=peaks(kind) if devices[0].platform != "cpu" else {},
              chips=len(devices), setup_s=setup_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = manifest.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": bool(correct), "attempted": len(win.counted),
           "failed": int(win.failed), "metrics": metrics, "device": device}
    if trace and tr is not None:
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        out["breakdown"] = {"device_ops": tr.device_ops,
                            "idle_gaps": tr.idle_gaps}
        log(f"trace: busy {tr.busy_s} s of {tr.window_s} s on "
            f"{tr.devices} device(s); host spans {tr.span_s}; device time "
            f"in them {tr.span_busy_s}; programs {tr.module_s}")
    if control:
        c_correct, c_checks = check.verdict(
            {"max_logit_gap": read.get("control_max_logit_gap", float("inf")),
             "tokens_compared": read.get("tokens_compared", 0)},
            True, cell.limits)
        out["control"] = {"correct": bool(c_correct),
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in c_checks.items()}}
        log(f"control: correct {c_correct}, {c_checks}")
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return out


def scratch_dir(root: str, cell: str) -> str:
    return os.path.join(root, ".bench_trace", cell)
