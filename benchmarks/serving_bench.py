"""Serving fast-path benchmark: the real-engine decode microbenches.

The serving hot paths (``InferenceEngine.generate`` fused scan,
``ContinuousServer`` fused multi-step chunks + bucketed batched admission)
are what the calibration layer measures and the platform bills, so their
throughput bounds every modern-substrate experiment.  This suite times them
on the reduced deepseek-7b config and writes ``BENCH_serving.json`` so the
serving perf trajectory is recorded PR over PR, exactly like
``simloop_bench`` does for the event loop:

  * ``engine.decode_tps``        — fused-scan generate, steady state
  * ``server.decode_tps_by_slots`` — fused server decode at 1/2/4 slots
  * ``server.steady_tps``        — the headline: slots=4 continuous serving,
                                   16 x 64-token requests (the gate metric)
  * ``server.admit_warm_s``      — one warm admission round (batched
                                   bucketed prefill + slot scatter)
  * ``*.compiles``               — live jit-cache sizes: recompiles show up
                                   as counts, not just lost wall time

Run:

    PYTHONPATH=src python -m benchmarks.serving_bench             # full
    PYTHONPATH=src python -m benchmarks.serving_bench --tiny      # CI smoke
    PYTHONPATH=src python -m benchmarks.serving_bench --tiny \
        --baseline benchmarks/baseline_serving.json --tolerance 0.30

Methodology: every timed section is preceded by an untimed warmup of the
same jitted calls (compiles are reported separately, in ``compiles`` and
``compile_s``) and repeated ``--trials`` times with the best kept — the
minimum is the run with the least interference on shared machines.

``--baseline`` turns the run into a perf-regression guard on
``server.steady_tps``: exits 2 when it falls more than ``--tolerance``
(default 30%; CI passes 50% — container CPUs are noisy) below the committed
baseline.  CI runs the tiny configuration on every push.

``llm_serving`` (the ``benchmarks.run`` table) pushes the measured engines
through the ``ServerlessPlatform``/``PolicyStack`` facade — the platform's
own deploy/invoke path, not the legacy single-function ``Simulator`` shim.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import ARCHS

ARCH = "deepseek-7b"


# ----------------------------------------------------------------------
# microbenches
# ----------------------------------------------------------------------

def bench_engine(cfg, *, batch: int, prompt: int, n_new: int,
                 trials: int) -> dict:
    """Steady-state fused-scan generate: tokens/s after the compile."""
    from repro.serving.engine import InferenceEngine
    eng = InferenceEngine(cfg, seed=0, max_cache=prompt + n_new + 16)
    toks = jnp.zeros((batch, prompt), jnp.int32)
    t0 = time.perf_counter()
    eng.generate(toks, n_new)                   # compile (untimed)
    compile_s = time.perf_counter() - t0
    best = 0.0
    for _ in range(max(1, trials)):
        r = eng.generate(toks, n_new)
        best = max(best, r.tokens_per_s)
    return {"decode_tps": round(best, 1), "prefill_s": round(r.prefill_s, 5),
            "compile_s": round(compile_s, 3), "compiles": eng.compile_stats()}


def _fill(srv, n, *, n_new, prompt_len: int = 8, rid0: int = 0):
    from repro.serving.continuous import Request
    for i in range(n):
        srv.submit(Request(rid=rid0 + i, prompt=[1 + (rid0 + i) % 7] *
                           prompt_len, n_new=n_new))


def bench_server_slots(cfg, slots: int, *, n_new: int, trials: int) -> dict:
    """Fused decode throughput with exactly ``slots`` active sequences
    (admission excluded: requests are prefilled before the clock starts)."""
    from repro.serving.continuous import ContinuousServer
    srv = ContinuousServer(cfg, slots=slots, max_seq=n_new + 16, seed=0)
    best = 0.0
    for t in range(max(2, trials)):             # trial 0 pays the compiles
        _fill(srv, slots, n_new=n_new, rid0=100 * t)
        srv.prefill_pending()
        n0 = srv.steps
        t0 = time.perf_counter()
        srv.run()
        wall = time.perf_counter() - t0
        best = max(best, (srv.steps - n0) * slots / wall)
    return {"decode_tps": round(best, 1), "compiles": srv.compile_stats()}


def bench_server_steady(cfg, *, slots: int, requests: int, n_new: int,
                        trials: int) -> dict:
    """The headline: continuous serving with slot refill — ``requests``
    requests drained through ``slots`` slots, tokens/s over the drain.
    (Setup mirrors the pre-fast-path measurement in DESIGN.md §4.)"""
    from repro.serving.continuous import ContinuousServer
    srv = ContinuousServer(cfg, slots=slots, max_seq=n_new + 32, seed=0)
    _fill(srv, slots, n_new=n_new)              # warmup: compiles, untimed
    srv.prefill_pending()
    srv.run()
    best = 0.0
    for t in range(max(1, trials)):
        _fill(srv, requests, n_new=n_new, rid0=1000 * (t + 1))
        n0 = srv.steps
        t0 = time.perf_counter()
        srv.run()
        wall = time.perf_counter() - t0
        best = max(best, (srv.steps - n0) * slots / wall)
    return {"steady_tps": round(best, 1), "slots": slots,
            "requests": requests, "n_new": n_new,
            "compiles": srv.compile_stats()}


def bench_admit(cfg, *, slots: int = 4, trials: int = 3) -> dict:
    """Warm admission latency: one batched bucketed prefill + one slot
    scatter for ``slots`` mixed-length prompts (lengths share a bucket, so
    warm rounds hit the compile cache)."""
    from repro.serving.continuous import ContinuousServer, Request
    srv = ContinuousServer(cfg, slots=slots, max_seq=64, seed=0)

    def round_(rid0):
        for i in range(slots):
            srv.submit(Request(rid=rid0 + i, prompt=[1 + i] * (5 + i),
                               n_new=2))
    round_(0)
    srv.prefill_pending()                       # cold: compiles (untimed)
    srv.run()
    best = float("inf")
    for t in range(max(1, trials)):
        round_(100 * (t + 1))
        t0 = time.perf_counter()
        srv.prefill_pending()
        best = min(best, time.perf_counter() - t0)
        srv.run()
    return {"admit_warm_s": round(best, 5), "slots": slots,
            "prefill_compiles": srv.compile_stats()["prefill"]}


def run_bench(*, tiny: bool, trials: int) -> dict:
    cfg = ARCHS[ARCH].smoke
    n_new = 16 if tiny else 64
    requests = 8 if tiny else 16
    t_all = time.perf_counter()
    engine = bench_engine(cfg, batch=4, prompt=16,
                          n_new=32 if tiny else 128, trials=trials)
    by_slots = {str(s): bench_server_slots(cfg, s, n_new=n_new,
                                           trials=trials)
                for s in (1, 2, 4)}
    steady = bench_server_steady(cfg, slots=4, requests=requests,
                                 n_new=n_new, trials=trials)
    admit = bench_admit(cfg, trials=trials)
    return {
        "arch": ARCH,
        "tiny": tiny,
        "engine": engine,
        "server": {"decode_tps_by_slots": by_slots, **steady, **admit},
        "steady_tps": steady["steady_tps"],     # the gate metric
        "wall_s": round(time.perf_counter() - t_all, 2),
    }


# ----------------------------------------------------------------------
# platform table (benchmarks.run) — through the ServerlessPlatform facade
# ----------------------------------------------------------------------

def llm_serving(arch_ids=("deepseek-7b", "rwkv6-1.6b",
                          "qwen3-moe-235b-a22b"), *, fallback: bool = True):
    """Modern engines as serverless functions: deploy each arch through the
    ``ServerlessPlatform`` (its calibrated handler + the platform's policy
    stack) and run the paper's warm-burst experiment.  ``fallback=False``
    measures the engines live via the calibration cache instead of the
    pinned numbers."""
    from repro.core.calibration import MODERN_MODELS, ensure_measured
    from repro.core.platform import ServerlessPlatform
    from repro.core.workload import warm_burst
    plat = ServerlessPlatform(seed=0, use_fallback_calibration=fallback)
    rows, lines = [], ["# Modern serving handlers on the serverless platform "
                       "(reduced configs): arch, cold_s, warm_s, tok/s"]
    for aid in arch_ids:
        spec = plat.deploy_model(aid, 1536)
        # no priming request: the first arrival IS the cold we report
        recs, sim = plat.invoke(spec, warm_burst(n=8, prime=False))
        warm = [r for r in recs if not r.cold]
        cold = [r for r in recs if r.cold]
        if fallback:
            m = MODERN_MODELS[aid]["fallback"]
        else:
            m = ensure_measured(None, aid)["models"][aid]["measured"]
        rows.append((f"serve/{aid}", warm[0].response_s * 1e6,
                     m["tokens_per_s"]))
        lines.append(f"  {aid:24s} cold={cold[0].response_s:6.2f}s "
                     f"warm={warm[0].response_s:6.3f}s "
                     f"tok/s={m['tokens_per_s']:7.1f} "
                     f"(compile={m['compile_s']:.2f}s)")
    return rows, "\n".join(lines)


# ----------------------------------------------------------------------
# CLI + regression gate
# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (16-token decodes, 8 requests)")
    ap.add_argument("--trials", type=int, default=3,
                    help="timed repetitions per section; best kept "
                         "(default 3)")
    ap.add_argument("--out", default="artifacts/BENCH_serving.json",
                    help="result JSON path")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to guard against; exits "
                         "2 when steady_tps regresses more than "
                         "--tolerance below it")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional regression vs --baseline "
                         "(default 0.30; CI uses 0.50)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    result = run_bench(tiny=args.tiny, trials=args.trials)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    srv = result["server"]
    print(f"[serving_bench] engine {result['engine']['decode_tps']:,.0f} "
          f"tok/s | server "
          + " ".join(f"x{s}={v['decode_tps']:,.0f}"
                     for s, v in srv["decode_tps_by_slots"].items())
          + f" | steady {result['steady_tps']:,.0f} tok/s "
          f"| admit {srv['admit_warm_s']*1e3:.1f}ms "
          f"| compiles {srv['compiles']} "
          f"({result['wall_s']:.1f}s); written to {args.out}")

    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        if bool(base.get("tiny")) != bool(args.tiny):
            ap.error(f"baseline {args.baseline} was measured with "
                     f"tiny={base.get('tiny')} — not comparable to this "
                     f"run (tiny={args.tiny})")
        floor = base["steady_tps"] * (1.0 - args.tolerance)
        verdict = "OK" if result["steady_tps"] >= floor else "REGRESSED"
        print(f"[serving_bench] perf guard: {result['steady_tps']:,.0f} vs "
              f"baseline {base['steady_tps']:,.0f} tok/s "
              f"(floor {floor:,.0f} at -{args.tolerance:.0%}) -> {verdict}")
        if verdict == "REGRESSED":
            return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
