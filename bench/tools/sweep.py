"""Find the highest rate an open-loop cell sustains: one server, one window
per offered rate, in one process.

    python3 bench/tools/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r>...

For each rate it prints one JSON line: time to first token (median, 90th
percentile, and the 90th percentile of the first and the last half of the
window's arrivals), the backlog left when the window closed, and how long
the queue took to drain after it.  The knee is the highest rate whose
backlog stays flat: the last half's tail no worse than the first's, and
little left at the close.  A cell runs below it, at a rate fixed in its mix.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    from bench import run
    got = run.prepare(args.workload)
    if isinstance(got, int):
        return got
    cell, _ = got
    from bench.lib import model, readings, serve, traffic, weights
    w = model.widths(cell.config)
    srv = serve.make_server(model.program_config(cell.config_name,
                                                 cell.config),
                            weights.build(args.seed, w), cell.mix)
    serve.warm_up(srv, cell.mix, w.vocab)
    span = serve.spans(False)
    for rate in args.rates:
        mix = {**cell.mix, "arrivals": {**cell.mix["arrivals"],
                                        "rate_rps": rate}}
        reqs = traffic.requests(mix, w.vocab, args.seconds, args.seed)
        win = serve.serve(srv, reqs, mix, args.seconds, span)
        logs = [win.logs[r] for r in win.counted]
        half = args.seconds / 2
        early = [(lg.first - lg.due) * 1e3 if lg.n else math.inf
                 for lg in logs if lg.due < half]
        late = [(lg.first - lg.due) * 1e3 if lg.n else math.inf
                for lg in logs if lg.due >= half]
        left = sum(1 for lg in logs if not lg.admit_start <= win.seconds)
        ttft = readings.ttfts_ms(win)
        print(json.dumps({
            "rate_rps": rate, "requests": len(logs), "failed": win.failed,
            "ttft_p50_ms": sorted(ttft)[len(ttft) // 2] if ttft else None,
            "ttft_p90_ms": readings.p90(ttft),
            "ttft_p90_first_half_ms": readings.p90(early),
            "ttft_p90_last_half_ms": readings.p90(late),
            "tpot_p90_ms": readings.p90(readings.tpots_ms(win)),
            "backlog_at_close": left,
            "drain_s": max(lg.last for lg in logs) - win.seconds,
            "compiles": win.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
