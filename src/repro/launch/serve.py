"""Serving launcher: batched generation over a request trace, optionally
through the serverless platform (cold/warm accounting).

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --smoke \
        --requests 12 --n-new 8 [--serverless]
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--n-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--serverless", action="store_true",
                    help="also run the measured engine through the platform")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.configs.registry import get
    from repro.serving.batcher import Batcher, PendingRequest
    from repro.serving.engine import InferenceEngine

    enable_compile_cache()
    spec = get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    eng = InferenceEngine(cfg, max_cache=args.prompt + args.n_new + 8)
    compile_s = eng.warmup(args.max_batch, args.prompt)
    print(f"[serve] {cfg.name}: load={eng.load_s:.2f}s "
          f"compile={compile_s:.2f}s")

    batcher = Batcher(max_batch=args.max_batch,
                      max_wait_s=args.max_wait_ms / 1e3)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        batcher.submit(PendingRequest(
            rid=rid,
            tokens=rng.integers(0, cfg.vocab_size, size=args.prompt).tolist(),
            arrival_s=time.perf_counter() - t0, n_new=args.n_new))
    lat, outs = {}, {}
    while batcher.queue:
        batch = batcher.form_batch(time.perf_counter() - t0, force=True)
        res = eng.generate(jnp.asarray(batch.tokens), batch.n_new,
                           temperature=args.temperature)
        done = time.perf_counter() - t0
        # the engine decodes the batch max; settle each request at its own
        # budget so a 2-token ask batched with a 64-token ask gets 2 tokens
        for i, rid in enumerate(batch.rids):
            lat[rid] = done
            outs[rid] = np.asarray(res.tokens[i, :batch.n_new_each[i]])
        print(f"[serve]   batch={len(batch.rids)} prefill="
              f"{res.prefill_s*1e3:.1f}ms decode={res.decode_s*1e3:.1f}ms "
              f"({res.tokens_per_s:.0f} tok/s)")
    toks_out = sum(len(v) for v in outs.values())
    print(f"[serve] {len(lat)} requests served ({toks_out} tokens); p50="
          f"{np.percentile(list(lat.values()), 50):.3f}s "
          f"max={max(lat.values()):.3f}s")

    if args.serverless:
        from repro.core.function import FunctionSpec
        from repro.core.simulator import Simulator
        from repro.core.workload import warm_burst
        from repro.serving.handler import llm_handler, measure_engine
        m = measure_engine(cfg, batch=args.max_batch, prompt=args.prompt,
                           n_new=args.n_new)
        fspec = FunctionSpec(handler=llm_handler(cfg, measured=m),
                             memory_mb=1536)
        sim = Simulator(fspec, seed=0, jitter=0.0)
        recs = sim.run(warm_burst(n=10))
        cold = [r for r in recs if r.cold][0]
        warm = [r for r in recs if not r.cold][0]
        print(f"[serve] serverless: cold={cold.response_s:.2f}s "
              f"warm={warm.response_s:.3f}s "
              f"(bimodality x{cold.response_s/warm.response_s:.1f})")


if __name__ == "__main__":
    main()
