"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per measured point) plus
human-readable blocks per figure.  Run:

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the real-engine serving benchmark")
    ap.add_argument("--fallback-calibration", action="store_true",
                    help="use the paper's 2017 timings instead of measuring")
    args = ap.parse_args()

    from benchmarks import (keepalive_study, paper_figs, policy_sweep,
                            roofline_report)
    from repro.core.platform import ServerlessPlatform

    plat = ServerlessPlatform(
        seed=0, use_fallback_calibration=args.fallback_calibration)

    all_rows = []
    blocks = []

    for fn in (paper_figs.table1_pricing,
               lambda: paper_figs.warm_figs(plat),
               lambda: paper_figs.cold_figs(plat),
               paper_figs.fig7_workload,
               lambda: paper_figs.scale_figs(plat),
               lambda: paper_figs.cold_phase_fig(plat),
               lambda: keepalive_study.ttl_frontier(plat),
               lambda: keepalive_study.prewarm_ablation(plat),
               lambda: policy_sweep.policy_sweep(plat),
               lambda: roofline_report.roofline(mesh_tag="single"),
               lambda: roofline_report.roofline(mesh_tag="multi")):
        rows, block = fn()
        all_rows.extend(rows)
        blocks.append(block)

    if not args.quick:
        # a failure here fails the run: the real-engine rows are the only
        # measured ones, and a silent skip would read as a passing bench
        from benchmarks import serving_bench
        rows, block = serving_bench.llm_serving()
        all_rows.extend(rows)
        blocks.append(block)

    print("\n\n".join(blocks))
    print("\n=== CSV (name,us_per_call,derived) ===")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"\n[benchmarks] {len(all_rows)} rows across "
          f"{len(blocks)} tables/figures", file=sys.stderr)


if __name__ == "__main__":
    main()
