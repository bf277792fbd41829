"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout of the repository.  It prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, ``breakdown`` when traced, and
``checks``, each number compared beside its limit; the same checks are the
last lines of standard error.  It exits non-zero, printing no result, where
JAX finds no accelerator or fewer chips than the cell asks for, or where the
program's sources are not beside it.  JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prepare(workload: str):
    """The cell and the devices it runs on, with the compile cache set; or
    an exit code where the program's sources or the chips are missing."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program's sources (src/repro) are not beside the "
              "benchmark; run from a checkout of the repository",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # the TPU runtime's logs would otherwise go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import harness, manifest
    cell = manifest.cell(workload, ROOT)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"bench: {workload} needs {cell.chips} accelerator chip(s); "
              f"JAX finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    harness.log(f"device {devices[0].device_kind!r} x{len(devices)}, jax "
                f"{jax.__version__}, compile cache "
                f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")
    return cell, devices


def main(argv=None) -> int:
    args = _args(argv)
    got = prepare(args.workload)
    if isinstance(got, int):
        return got
    cell, devices = got
    from bench.lib import harness
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, STARTED,
                           harness.scratch_dir(ROOT, args.workload))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
