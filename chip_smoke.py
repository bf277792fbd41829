#!/usr/bin/env python3
"""Drive the served path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: Pallas kernels, then
                                     # ContinuousServer on deepseek-7b's
                                     # one-chip cut
    python chip_smoke.py --chips 4   # four chips only: mistral-nemo-12b,
                                     # tensor parallel over a (1, 4) mesh

Everything runs in this one process, which holds the chip(s).  The script
refuses to start unless JAX's first device is a TPU (it never falls back to
the CPU), and it needs the repository's ``src/`` beside it.  Every phase
either passes its checks or raises, so the exit code is non-zero.  The last
line of standard output is one JSON object naming the device.

Weights are random from ``--seed``; so are the prompts.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

# Kernel shapes at real widths: deepseek-7b attention (32 heads x 128,
# MHA) and rwkv6-1.6b's WKV (32 heads x 64).
KERNEL_SHAPES = {
    "flash_attention": dict(b=2, s=1024, h=32, kh=32, hd=128),
    "flash_decode": dict(b=4, s=1024, h=32, kh=32, hd=128),
    "wkv6": dict(b=1, t=512, h=32, hd=64),
}

# Kernel tolerances, as a share of the reference's largest magnitude.
# bf16 kernels (flash attention / decode) take bf16 q, k, v, keep the
# softmax and the PV product in float32, and round their output to 8
# mantissa bits (2^-8 relative); 2^-6 leaves 4x for accumulation order, and
# a kernel computing in fp8 (2^-4) would fail it.  wkv6 runs in float32
# throughout: its error is summation order (~1e-6); 1e-4 would catch any
# bf16 step (2^-8).
BF16_KERNEL_TOL = 2.0 ** -6
F32_KERNEL_TOL = 1e-4

# Served-logit tolerances for bfloat16 configs, as a share of the std of
# the reference logits (about 1 with these random weights).  Two paths that
# compute the same logits differently (through the cache or not, on one
# chip or tensor parallel) round different intermediate sums to bf16 (8
# mantissa bits); a 1-ulp flip early in a layer stack spreads to every
# later position, and the flips of independent layers add up: the mean
# |diff| grows about as sqrt(depth) (on a TPU v5e, 0.0115 at 16 layers and
# 0.0174 at 40).  Mean |diff| <= 2^-5 holds for bf16 rounding up to ~100
# layers and fails for fp8 rounding (5 fewer mantissa bits, 32x the noise)
# or a wrong cache position or mask (differences of a whole std).  The
# largest of ~10M compared logits has stayed within 7x the mean: 2^-2.  A
# served token may trail the reference's best logit by at most 2^-2; a
# wrongly chosen token trails it by a sizeable share of the std.
MEAN_TOL = 2.0 ** -5
MAX_TOL = 2.0 ** -2
MARGIN_TOL = 2.0 ** -2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ----------------------------------------------------------------------
# phase 1: the Pallas kernels, compiled for the chip, against ref.py
# ----------------------------------------------------------------------

def kernel_phase(seed: int, shapes: dict = KERNEL_SHAPES,
                 interpret: bool = False) -> dict:
    """Each kernel at ``shapes`` against its ``ref.py`` oracle computed in
    float32 at full matmul precision.  Returns the max error per kernel."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention.ops import flash_attention
    from repro.kernels.attention.ref import flash_attention_ref
    from repro.kernels.decode.ops import flash_decode
    from repro.kernels.decode.ref import flash_decode_ref
    from repro.kernels.rwkv.ops import wkv6
    from repro.kernels.rwkv.ref import wkv6_ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    errs = {}

    def compare(name, out, ref, tol):
        out, ref = (np.asarray(jnp.asarray(x, jnp.float32)) for x in (out, ref))
        check(out.shape == ref.shape and np.isfinite(out).all(),
              f"{name}: shape {out.shape} vs {ref.shape} or non-finite")
        scale = float(np.abs(ref).max())
        err = float(np.abs(out - ref).max())
        errs[name] = err
        log(f"kernel {name}: max|err|={err!r} (limit {tol * scale!r})")
        check(err <= tol * scale, f"{name}: max error {err} > {tol * scale}")

    # the references run in float32 at full matmul precision; the kernels
    # run outside that context (Mosaic refuses a bf16 matmul asked for fp32
    # contract precision)
    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    a = shapes["flash_attention"]
    q = rand((a["b"], a["s"], a["h"], a["hd"]))
    k, v = (rand((a["b"], a["s"], a["kh"], a["hd"])) for _ in range(2))
    compare("flash_attention", flash_attention(q, k, v, interpret=interpret),
            ref(flash_attention_ref, *f32(q, k, v)), BF16_KERNEL_TOL)

    d = shapes["flash_decode"]
    q = rand((d["b"], 1, d["h"], d["hd"]))
    ck, cv = (rand((d["b"], d["s"], d["kh"], d["hd"])) for _ in range(2))
    valid = jnp.arange(d["s"]) < (2 * d["s"]) // 3
    compare("flash_decode", flash_decode(q, ck, cv, valid, interpret=interpret),
            ref(flash_decode_ref, *f32(q, ck, cv), valid), BF16_KERNEL_TOL)

    w_ = shapes["wkv6"]
    shape = (w_["b"], w_["t"], w_["h"], w_["hd"])
    r, k, v = (rand(shape, jnp.float32) for _ in range(3))
    w = jnp.exp(-jnp.exp(rand(shape, jnp.float32) - 2.0))
    u = rand((w_["h"], w_["hd"]), jnp.float32) * 0.5
    s0 = rand((w_["b"], w_["h"], w_["hd"], w_["hd"]), jnp.float32) * 0.1
    o, sf = wkv6(r, k, v, w, u, s0, interpret=interpret)
    oref, sref = ref(wkv6_ref, r, k, v, w, u, s0)
    compare("wkv6_out", o, oref, F32_KERNEL_TOL)
    compare("wkv6_state", sf, sref, F32_KERNEL_TOL)
    return errs


# ----------------------------------------------------------------------
# logits of one path against another's
# ----------------------------------------------------------------------

def logit_agreement(tag: str, rows) -> dict:
    """``rows``: per request (tokens, logits, reference logits), the logits
    (len(tokens), V), the row that chose each token.  Checks their mean and
    largest |diff| and how far each token trails the reference's best, all
    as shares of the reference logits' std."""
    from repro.serving import logit_check
    diffs, stds, margins = [], [], []
    for toks, got, ref in rows:
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        check(got.shape == ref.shape == (len(toks), ref.shape[-1]),
              f"{tag}: logits {got.shape} vs {ref.shape}")
        check(np.isfinite(got).all() and np.isfinite(ref).all(),
              f"{tag}: non-finite logits")
        diffs.append(np.abs(got - ref))
        stds.append(float(ref.std()))
        margins.append(logit_check.token_margins(ref, toks))
    std = float(np.mean(stds))
    out = {"positions": int(sum(len(d) for d in diffs)),
           "logit_std": std,
           "max_diff": float(max(d.max() for d in diffs)),
           "mean_diff": float(np.mean([d.mean() for d in diffs])),
           "max_margin": float(max(m.max() for m in margins))}
    log(f"{tag}: {out} (limits mean {MEAN_TOL * std!r} max {MAX_TOL * std!r}"
        f" margin {MARGIN_TOL * std!r})")
    check(out["mean_diff"] <= MEAN_TOL * std, f"{tag}: mean logit diff")
    check(out["max_diff"] <= MAX_TOL * std, f"{tag}: max logit diff")
    check(out["max_margin"] <= MARGIN_TOL * std, f"{tag}: token margin")
    return out


def peak_hbm(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------------
# phase 2: ContinuousServer on one chip
# ----------------------------------------------------------------------

def server_phase(cfg, *, slots: int, max_seq: int, seed: int,
                 n_requests: int = 8, prompt_range=(100, 501),
                 n_new: int = 32) -> dict:
    """Serve ``n_requests`` seeded prompts through ContinuousServer three
    times: the first drain compiles every prefill bucket and decode chunk
    the traffic needs, the second runs warm and must emit the same tokens,
    and the third keeps the logits the server chose each token from, which
    must agree with the cache-free forward pass."""
    import jax

    from repro.serving import logit_check
    from repro.serving.continuous import ContinuousServer, Request

    t0 = time.perf_counter()
    srv = ContinuousServer(cfg, slots=slots, max_seq=max_seq, seed=seed)
    jax.block_until_ready((srv.params, srv.cache))
    init_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(*prompt_range))).tolist()
               for _ in range(n_requests)]

    def drain():
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=list(p), n_new=n_new))
        t = time.perf_counter()
        done = srv.run()
        return time.perf_counter() - t, {c.rid: c for c in done}

    cold_s, first = drain()
    compiles = srv.compile_stats()
    warm_s, second = drain()
    check(srv.compile_stats() == compiles, "the warm drain recompiled")
    tokens = {i: c.tokens for i, c in second.items()}
    check({i: c.tokens for i, c in first.items()} == tokens,
          "the same requests gave different tokens")
    tokens_out = sum(len(t) for t in tokens.values())
    check(tokens_out == n_requests * n_new, f"tokens out {tokens_out}")
    check(all(0 <= t < cfg.vocab_size for ts in tokens.values() for t in ts),
          "token id out of range")
    res = {"init_s": init_s, "cold_drain_s": cold_s, "warm_drain_s": warm_s,
           "compile_s": cold_s - warm_s, "tokens_out": tokens_out,
           "warm_tokens_per_s": tokens_out / warm_s, "compiles": compiles,
           "prompt_lens": [len(p) for p in prompts],
           "peak_hbm_bytes": peak_hbm(jax.devices()[0])}
    log(f"server {cfg.name}: {res}")

    # the same admission and scatter; the fused step also returns logits
    srv.keep_logits = True
    _, kept = drain()
    check({i: c.tokens for i, c in kept.items()} == tokens,
          "keeping the logits changed the served tokens")
    res["agreement"] = logit_agreement("served vs forward", [
        (c.tokens, c.logits,
         logit_check.generated_logits(srv.params, cfg, prompts[i], c.tokens))
        for i, c in sorted(kept.items())])
    return res


# ----------------------------------------------------------------------
# four chips: tensor-parallel mistral-nemo-12b
# ----------------------------------------------------------------------

def device_bytes(params) -> dict:
    """Bytes of ``params`` resident on each device."""
    import jax
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def four_chip_phase(devices, seed: int, full_cfg=None, cut_layers: int = 8,
                    batch: int = 4, prompt_len: int = 128,
                    n_new: int = 16) -> dict:
    """mistral-nemo-12b at full size, tensor parallel over a (1, 4) mesh
    through ``InferenceEngine(mesh=...)``, its served logits against its
    forward pass; then the same model cut to ``cut_layers`` (which one chip
    holds) on four chips and on one: served logits against served logits,
    forward against forward."""
    import jax
    import jax.numpy as jnp

    from repro.configs import mistral_nemo_12b
    from repro.launch.mesh import make_local_mesh
    from repro.serving import logit_check
    from repro.serving.engine import InferenceEngine

    cfg = full_cfg or mistral_nemo_12b.CONFIG
    mesh = make_local_mesh(1, len(devices), devices=devices)
    max_cache = prompt_len + n_new
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len))

    eng = InferenceEngine(cfg, seed=seed, mesh=mesh, max_cache=max_cache)
    per_dev = device_bytes(eng.params)
    total = sum(per_dev.values())
    res = {"model": cfg.name, "load_s": eng.load_s, "param_bytes": total,
           "bytes_per_device": {str(d): b for d, b in per_dev.items()},
           "bytes_in_use": {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                            for d in devices}}
    log(f"tp{len(devices)} {cfg.name}: load_s={eng.load_s!r} "
        f"param_bytes={total} per device {res['bytes_per_device']}")
    check(len(per_dev) == len(devices), "params are not on every device")
    check(max(per_dev.values()) <= 1.1 * total / len(devices),
          "one device holds more than its share of the weights")

    toks = jnp.asarray(prompts, jnp.int32)
    t = time.perf_counter()
    cold = eng.generate(toks, n_new)
    res["cold_generate_s"] = time.perf_counter() - t
    warm = eng.generate(toks, n_new)
    out = np.asarray(warm.tokens)
    check(np.array_equal(np.asarray(cold.tokens), out),
          "the same prompts gave different tokens")
    check(out.shape == (batch, n_new), f"tokens shape {out.shape}")
    res.update(prefill_s=warm.prefill_s, decode_s=warm.decode_s,
               decode_tokens_per_s=warm.tokens_per_s)
    log(f"tp{len(devices)} {cfg.name} generate: cold "
        f"{res['cold_generate_s']!r}s, warm prefill {warm.prefill_s!r}s "
        f"decode {warm.decode_s!r}s ({warm.tokens_per_s!r} tok/s)")
    kept = eng.generate(toks, n_new, keep_logits=True)
    check(np.array_equal(np.asarray(kept.tokens), out),
          "keeping the logits changed the served tokens")
    res["served_vs_forward"] = logit_agreement(
        f"tp{len(devices)} {cfg.name} served vs forward", [
            (g, lg, logit_check.generated_logits(eng.params, cfg, p, g))
            for p, g, lg in zip(prompts.tolist(), out.tolist(),
                                np.asarray(kept.logits))])
    del eng, cold, warm, kept
    gc.collect()

    # 4 chips vs 1 at a depth one chip holds: the served (cached) logits of
    # each, and the forward pass of each over the four-chip tokens
    cut = cfg.replace(num_layers=cut_layers)
    tp = InferenceEngine(cut, seed=seed, mesh=mesh, max_cache=max_cache)
    single = InferenceEngine(cut, seed=seed, max_cache=max_cache)
    check(set(device_bytes(single.params)) == {jax.devices()[0]},
          "the one-chip engine is not on one chip")
    tp_out = tp.generate(toks, n_new, keep_logits=True)
    one_out = single.generate(toks, n_new, keep_logits=True)
    tp_toks, one_toks = (np.asarray(r.tokens) for r in (tp_out, one_out))
    rows = []
    for b_ in range(batch):
        # the streams are comparable up to and including the first token
        # they chose differently
        differ = np.flatnonzero(tp_toks[b_] != one_toks[b_])
        n = differ[0] + 1 if len(differ) else n_new
        rows.append((tp_toks[b_, :n], np.asarray(tp_out.logits[b_, :n]),
                     np.asarray(one_out.logits[b_, :n])))
    res["tp_vs_one_served"] = logit_agreement(
        f"tp{len(devices)} vs 1 chip served ({cut_layers} layers)", rows)
    res["tp_vs_one_forward"] = logit_agreement(
        f"tp{len(devices)} vs 1 chip forward ({cut_layers} layers)", [
            (g, logit_check.generated_logits(tp.params, cut, p, g),
             logit_check.generated_logits(single.params, cut, p, g))
            for p, g in zip(prompts.tolist(), tp_toks.tolist())])
    res["peak_hbm_bytes"] = {str(d): peak_hbm(d) for d in devices}
    log(f"peak HBM per device {res['peak_hbm_bytes']}")
    return res


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isfile(os.path.join(src, "repro", "models", "api.py")):
        print("[chip_smoke] FAIL: the repository's src/ is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] FAIL: JAX found no TPU (first device: "
              f"{devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] FAIL: {args.chips} chips asked, "
              f"{len(devices)} present", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"device kind={devices[0].device_kind!r} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")

    if args.chips == 4:
        four_chip_phase(devices[:4], args.seed)
    else:
        from repro.configs import deepseek_7b
        kernel_phase(args.seed)
        server_phase(deepseek_7b.ONE_CHIP, slots=deepseek_7b.ONE_CHIP_SLOTS,
                     max_seq=deepseek_7b.ONE_CHIP_MAX_SEQ, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
