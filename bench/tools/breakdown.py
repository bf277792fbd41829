"""One traced run of a cell, with the served program's own breakdown beside
the benchmark's result.

    python3 bench/tools/breakdown.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s own (``harness.run_cell``).  The
last line of standard output is its result line with three more keys:
``counters``, the server's work counts at the window's close less at its
open; ``prefill_pad_share``, the share of prefilled tokens that were
padding, in %; and ``inside`` (``bench/lib/program_trace.py``): device-0
idle put down to the innermost ``serve.*`` or ``bench.*`` span, host time
in each ``serve.*`` span, idle inside decode per step, decode's device
time by scope and its largest operations, and the span that held the most
host time in each of the window's slowest loop passes.  Against a program
without the spans, scopes or counters those parts are null or empty.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _counts(srv):
    c = getattr(srv, "counters", None)
    return dataclasses.asdict(c) if c is not None else None


@contextlib.contextmanager
def _hooked(seen: dict):
    """While open, ``run_cell``'s window also copies the server's counters
    as it opens and closes, and its trace is read by ``program_trace``
    before the harness deletes it; both land in ``seen``."""
    from bench.lib import program_trace, serve
    from bench.lib import trace as trace_lib
    serve_, read_ = serve.serve, trace_lib.read_xplane

    def serving(srv, reqs, mix, seconds, span, *rest):
        @contextlib.contextmanager
        def window(name):
            with span(name):
                seen["open"] = _counts(srv)
                try:
                    yield
                finally:
                    seen["close"] = _counts(srv)

        seen["window"] = serve_(
            srv, reqs, mix, seconds,
            lambda name: window(name) if name == trace_lib.WINDOW
            else span(name), *rest)
        return seen["window"]

    def reading(path):
        planes = read_(path)
        seen["inside"] = program_trace.breakdown(
            planes, program_trace.spans(path),
            program_trace.scope_paths(path, planes), seen["window"].slow)
        return planes

    serve.serve, trace_lib.read_xplane = serving, reading
    try:
        yield
    finally:
        serve.serve, trace_lib.read_xplane = serve_, read_


def traced(cell, seed: int, seconds: float, devices, scratch: str) -> dict:
    """``run_cell``'s traced result line, with the program's breakdown."""
    from bench.lib import harness
    seen = {}
    with _hooked(seen):
        out = harness.run_cell(cell, seed, seconds, True, devices, STARTED,
                               scratch)
    a, b = seen.get("open"), seen.get("close")
    counts = {k: b[k] - a[k] for k in b} if a and b else None
    out["counters"] = counts
    out["prefill_pad_share"] = (
        (counts["prefill_tokens"] - counts["prompt_tokens"])
        / counts["prefill_tokens"] * 100
        if counts and counts["prefill_tokens"] else None)
    out["inside"] = seen.get("inside")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    from bench import run
    got = run.prepare(args.workload)
    if isinstance(got, int):
        return got
    cell, devices = got
    from bench.lib import harness
    out = traced(cell, args.seed, args.seconds, devices,
                 harness.scratch_dir(ROOT, args.workload))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
