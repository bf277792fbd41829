"""InferenceEngine: the modern model-serving runtime.

Wraps any registered architecture behind prefill/decode steps (jit'd once —
the compile is the 'cold start' of the modern substrate, measured and fed to
the serverless platform via ``repro.serving.handler``).  Mesh-aware: pass a
mesh to shard params/caches with the production rules.

Decode fast path (DESIGN.md §4): ``generate()`` lowers the whole decode to a
single jitted ``lax.scan`` — sampling and RNG splitting run inside the scanned
body, the KV cache is donated so XLA updates it in place instead of
double-buffering the full (L,B,S,K,hd) tensor every step, and exactly one
``block_until_ready`` + device→host transfer happens at the end.  The legacy
per-token loop survives as ``generate_stream()`` for per-token latency
measurement (calibration).  Prompt lengths are bucketed to powers of two on
causal-attention configs so the prefill jit compiles per bucket, not per
unique length (MoE routing sees pad tokens — expert capacity is
length-sensitive — so MoE prompts stay exact; recurrent/windowed families
keep their exact shapes too).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import shardctx
from repro.configs.base import ArchSpec
from repro.models import api
from repro.models.common import ModelConfig, count_params
from repro.serving.sampler import sample_token


def bucket_len(n: int) -> int:
    """Smallest power of two >= n — the prompt-length bucket."""
    return max(1, 1 << (int(n) - 1).bit_length())


def pin_logits(logits):
    """Write a decode step's logits out whole before a token is picked.
    Without the barrier XLA fuses the vocabulary matmul into its consumer —
    the argmax alone, or the argmax and the kept copy with ``keep_logits``
    — and the two fusions tile the matmul's accumulation differently.  On
    the TPU that moves a few bf16 logits by one ulp, enough to flip a
    near-tied argmax, so keeping the logits would change the tokens."""
    return jax.lax.optimization_barrier(logits)


@dataclasses.dataclass
class GenerateResult:
    tokens: "jnp.ndarray"          # (B, n_new)
    prefill_s: float
    decode_s: float
    tokens_per_s: float
    token_walls: Optional[list] = None   # per-token decode walls (stream path)
    # (B, n_new, V) float32: the logits that chose each token, when kept
    logits: Optional["jnp.ndarray"] = None


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, *, seed: int = 0, mesh=None,
                 max_cache: int = 256):
        self.cfg = cfg
        self.mesh = mesh
        self.max_cache = max_cache
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(
            api.build_params(cfg, seed, self._param_shardings()))
        self.load_s = time.perf_counter() - t0
        self._prefill = jax.jit(self._prefill_impl, static_argnames=("cache_len",))
        self._decode = jax.jit(self._decode_impl)
        # the fused decode: one jitted scan per (n_steps, temperature);
        # the cache argument is donated so XLA aliases it in place
        self._decode_scan = jax.jit(
            self._decode_scan_impl, donate_argnums=(1,),
            static_argnames=("n_steps", "temperature", "keep_logits"))
        self.compiled = False
        self.compile_s = 0.0

    def _param_shardings(self):
        """Each leaf's place on the mesh (tensor parallel over "model",
        by ``launch.sharding.param_pspecs``); None without a mesh."""
        if self.mesh is None:
            return None
        from repro.launch import sharding
        specs = sharding.param_pspecs(api.abstract_params(self.cfg),
                                      self.cfg, self.mesh)
        return sharding.to_named(specs, self.mesh)

    # ------------------------------------------------------------------
    def _prefill_impl(self, params, inputs, cache_len, last_pos=None):
        with shardctx.use_mesh(self.mesh):
            return api.prefill(params, inputs, self.cfg, cache_len,
                               last_pos=last_pos)

    def _decode_impl(self, params, cache, token, pos):
        with shardctx.use_mesh(self.mesh):
            return api.decode_step(params, cache, token, pos, self.cfg)

    def _decode_scan_impl(self, params, cache, tok, pos, rng, *,
                          n_steps: int, temperature: float,
                          keep_logits: bool = False):
        """Fused decode: n_steps of (decode_step -> sample) under one jit.
        The RNG key sequence is bit-identical to the per-token loop's
        (split once per step; greedy ignores the subkeys entirely).
        ``keep_logits`` also returns each step's (B, V) float32 logits."""
        def body(carry, _):
            cache, tok, pos, rng = carry
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode_impl(params, cache, tok, pos)
            logits = pin_logits(logits)
            nxt = sample_token(logits, temperature, sub)
            out = (nxt, logits.astype(jnp.float32)) if keep_logits else nxt
            return (cache, nxt, pos + 1, rng), out
        (cache, tok, pos, rng), toks = jax.lax.scan(
            body, (cache, tok, pos, rng), None, length=n_steps)
        return toks, cache          # toks: (n_steps, B)

    # ------------------------------------------------------------------
    def warmup(self, batch: int, prompt_len: int):
        """Compile both steps — the modern 'cold start'."""
        t0 = time.perf_counter()
        inputs = {"tokens": jnp.zeros((batch, prompt_len), jnp.int32)}
        self._add_modal(inputs, batch)
        _, cache = self._prefill(self.params, inputs, cache_len=self.max_cache)
        _ = self._decode(self.params, cache, jnp.zeros((batch,), jnp.int32),
                         jnp.int32(prompt_len))
        jax.block_until_ready(_)
        self.compile_s = time.perf_counter() - t0
        self.compiled = True
        return self.compile_s

    def _add_modal(self, inputs: dict, batch: int):
        cfg = self.cfg
        if cfg.family == "audio":
            inputs["frame_embeds"] = jnp.zeros(
                (batch, cfg.encoder_seq, cfg.d_model), cfg.cdt)
        if cfg.family == "vlm":
            inputs["patch_embeds"] = jnp.zeros(
                (batch, cfg.num_image_tokens, cfg.d_model), cfg.cdt)

    def _prefill_shapes(self, s: int, n_new: int) -> tuple:
        """(padded_prompt_len, cache_len) — the recompile policy.

        dense: prompts pad to a power-of-two bucket and the cache is always
        ``max_cache``, so the prefill jit compiles once per bucket and the
        decode scan once per (n_steps) — not once per unique (s, n_new).
        moe: exact prompt (pad tokens shift expert routing) but the fixed
        cache still kills the n_new-driven recompiles.  Recurrent /
        windowed families keep the legacy exact shapes (their state is
        length- and window-sensitive)."""
        if self.cfg.family == "dense":
            return min(bucket_len(s), self.max_cache), self.max_cache
        if self.cfg.family == "moe":
            return s, self.max_cache
        return s, min(self.max_cache, s + n_new)

    # ------------------------------------------------------------------
    def generate(self, tokens: jnp.ndarray, n_new: int, *,
                 temperature: float = 0.0, seed: int = 0,
                 keep_logits: bool = False) -> GenerateResult:
        """tokens: (B, S) prompt.  Greedy/temperature decoding of n_new.

        Fused path: one prefill dispatch + one scanned decode dispatch +
        one device→host transfer, regardless of n_new.  ``keep_logits``
        also returns the logits that chose each token."""
        b, s = tokens.shape
        s_pad, cache_len = self._prefill_shapes(s, n_new)
        if s_pad > s:
            tokens = jnp.pad(tokens, [(0, 0), (0, s_pad - s)])
        inputs = {"tokens": tokens}
        self._add_modal(inputs, b)
        last_pos = jnp.int32(s - 1) if s_pad > s else None
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, inputs,
                                      cache_len=cache_len, last_pos=last_pos)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0

        rng = jax.random.PRNGKey(seed)
        tok = sample_token(logits, temperature, rng)
        t0 = time.perf_counter()
        kept = logits.astype(jnp.float32)[:, None] if keep_logits else None
        if n_new > 1:
            rest, _cache = self._decode_scan(
                self.params, cache, tok, jnp.int32(s), rng,
                n_steps=n_new - 1, temperature=float(temperature),
                keep_logits=keep_logits)
            if keep_logits:
                rest, rest_logits = rest
                kept = jnp.concatenate(
                    [kept, jnp.swapaxes(rest_logits, 0, 1)], axis=1)
            toks = jnp.concatenate([tok[:, None], rest.T], axis=1)
        else:
            toks = tok[:, None]
        toks = jax.block_until_ready(toks)     # the single host sync
        decode_s = time.perf_counter() - t0
        tps = (b * max(n_new - 1, 1)) / max(decode_s, 1e-9)
        return GenerateResult(tokens=toks, prefill_s=prefill_s,
                              decode_s=decode_s, tokens_per_s=tps,
                              logits=kept)

    def generate_stream(self, tokens: jnp.ndarray, n_new: int, *,
                        temperature: float = 0.0,
                        seed: int = 0) -> GenerateResult:
        """Per-token decoding (the legacy loop): one jitted call + host
        sync per token.  Slower than ``generate`` by construction — kept
        so calibration can time *per-token* latency, and as the parity
        reference for the fused scan (same token stream, pinned in
        tests)."""
        b, s = tokens.shape
        s_pad, cache_len = self._prefill_shapes(s, n_new)
        if s_pad > s:
            tokens = jnp.pad(tokens, [(0, 0), (0, s_pad - s)])
        inputs = {"tokens": tokens}
        self._add_modal(inputs, b)
        last_pos = jnp.int32(s - 1) if s_pad > s else None
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, inputs,
                                      cache_len=cache_len, last_pos=last_pos)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0

        rng = jax.random.PRNGKey(seed)
        out, walls = [], []
        tok = sample_token(logits, temperature, rng)
        out.append(tok)
        t0 = time.perf_counter()
        prev = t0
        for i in range(n_new - 1):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, cache, tok,
                                         jnp.int32(s + i))
            tok = sample_token(logits, temperature, sub)
            tok.block_until_ready()                  # per-token latency
            now = time.perf_counter()
            walls.append(now - prev)
            prev = now
            out.append(tok)
        decode_s = time.perf_counter() - t0
        toks = jnp.stack(out, axis=1)
        tps = (b * max(n_new - 1, 1)) / max(decode_s, 1e-9)
        return GenerateResult(tokens=toks, prefill_s=prefill_s,
                              decode_s=decode_s, tokens_per_s=tps,
                              token_walls=walls)

    # ------------------------------------------------------------------
    def compile_stats(self) -> dict:
        """Live jit-cache sizes — the recompile counters the serving bench
        and the bucketing tests assert on."""
        return {"prefill": self._prefill._cache_size(),
                "decode": self._decode._cache_size(),
                "decode_scan": self._decode_scan._cache_size()}

    def stats(self) -> dict:
        return {"arch": self.cfg.name, "params": count_params(self.params),
                "load_s": self.load_s, "compile_s": self.compile_s}
