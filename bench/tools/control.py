"""The readings a cell's limit is set between, on the chip, in one process.

    python3 bench/tools/control.py --workload <cell> --seconds <s> --seeds <n>...

For each seed it makes one run as ``bench/run.py`` does, at the cell's own
sizes and load, and reads under the float32 reference both the widest gap of
the tokens the program served and the widest gap of the tokens the control
(the reference computed on float8 operands) puts first at the same
positions, and holds each to the cell's limits: the run's verdict is
``correct``, the control's ``control.correct``.  It prints one JSON line per
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from bench import run
    got = run.prepare(args.workload)
    if isinstance(got, int):
        return got
    cell, devices = got
    from bench.lib import harness
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, devices,
                               time.perf_counter(),
                               harness.scratch_dir(ROOT, args.workload),
                               control=True)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
