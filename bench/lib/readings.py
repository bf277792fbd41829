"""Arithmetic that several metric readers share: tails over the window's
requests, the decode steps' operation counts, and device time from the
trace.  Every reader returns None where the run holds nothing to read."""
from __future__ import annotations

import math

import numpy as np

from bench.lib import counts

MS = 1e3
PCT = 1e2


def p90(values) -> float | None:
    """The 90th percentile (linear between order statistics) of all the
    values; None where there are none, or where it falls on a request that
    never finished (an infinite value)."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, float), 90))
    return v if math.isfinite(v) else None


def ttfts_ms(win) -> list:
    """Time to first token of every request due in the window, from the
    time it was due; a request that never got one counts as infinite."""
    out = []
    for rid in win.counted:
        lg = win.logs[rid]
        if lg.due is None:
            return []
        out.append((lg.first - lg.due) * MS if lg.n else math.inf)
    return out


def tpots_ms(win) -> list:
    """(last token - first token) / (tokens - 1) of every request due in
    the window that has two tokens or more; unfinished ones are infinite."""
    out = []
    for rid in win.counted:
        lg = win.logs[rid]
        if lg.due is None:
            return []
        if lg.n_new < 2:
            continue
        out.append((lg.last - lg.first) / (lg.n - 1) * MS
                   if lg.done else math.inf)
    return out


def span_device_s(run, span: str) -> float | None:
    """Device busy seconds inside the harness's ``span`` on chip 0."""
    if run.trace is None:
        return None
    got = run.trace.span_busy_s.get(span, 0.0)
    return got if got > 0 else None


def decode_steps(run) -> tuple[int, float, float] | None:
    """(steps, FLOPs, least seconds by the roofline) of the window's decode
    steps; None where it took none."""
    steps = getattr(run.window, "steps", None)
    if not steps or not run.peaks:
        return None
    flops = least = 0.0
    for n, attended in steps:
        f, b = counts.decode_step(run.w, n, attended)
        flops += f
        least += max(f / (run.peaks["bf16_flops"] * run.chips),
                     b / (run.peaks["hbm_bytes_per_s"] * run.chips))
    return len(steps), flops, least


def decode_step_ms(run) -> float | None:
    dev, got = span_device_s(run, "bench.step"), decode_steps(run)
    if dev is None or got is None:
        return None
    return dev / got[0] * MS


def decode_roofline(run) -> float | None:
    dev, got = span_device_s(run, "bench.step"), decode_steps(run)
    if dev is None or got is None:
        return None
    return got[2] / dev * PCT


def decode_mfu(run) -> float | None:
    dev, got = span_device_s(run, "bench.step"), decode_steps(run)
    if dev is None or got is None:
        return None
    return got[1] / (dev * run.peaks["bf16_flops"] * run.chips) * PCT


def idle_share(run) -> float | None:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return (t.window_s - t.busy_s) / t.window_s * PCT

