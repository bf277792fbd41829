"""Continuous batching (slot-based, vLLM-style scheduling).

The fixed-size decode batch is a set of *slots*; sequences at different
positions decode together using the vector-position decode path
(``attention_decode`` with per-row positions).  When a sequence finishes its
slot is immediately refilled from the queue — no waiting for the whole batch,
which is what turns the paper's per-request serving economics into sustained
throughput (DESIGN.md §4, "batching is first-class").

Decode fast path (DESIGN.md §4): compute state (KV cache, last tokens,
per-row positions) lives on device and is threaded through a donated, jitted
fused step — ``run()`` scans ``min(remaining)`` steps per dispatch
(decomposed into power-of-two chunks so the scan compiles O(log) times, not
per distinct length) and fetches the whole token block in ONE device→host
transfer.  Control state (``active``/``remaining``/``rid``) is host-side
bookkeeping that evolves deterministically — scheduling never syncs the
device.  Admission runs ONE batched prefill per round (prompts right-padded
to a power-of-two bucket on dense configs, so the prefill jit compiles per
bucket instead of per unique prompt length) and ONE donated slot-scatter —
not a full-cache copy per request.  MoE configs keep exact-length
per-request prefills (expert-capacity routing sees pad tokens, which would
change real tokens' routing) but still share the per-round scatter.

Transformer-family models (dense / moe / vlm).  Greedy decoding.
``repro.core.calibration`` drives this server to measure per-model
batch-efficiency curves (fused-step wall time at a pinned slot count).

Instrumentation: host spans (``jax.profiler.TraceAnnotation``, nothing
unless a profiler runs) named ``serve.admit`` and ``serve.decode``, each
with children for the phases where the host dispatches, waits for the
device or does its own bookkeeping; the device programs carry names
(``jit_admit_prefill``, ``jit_slot_scatter``, ``jit__fused_impl``) and
``jax.named_scope`` paths; ``counters`` holds the work counts.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.common import ModelConfig
from repro.models.transformer import DECODE_SCOPES
from repro.serving.engine import bucket_len, pin_logits

span = jax.profiler.TraceAnnotation
# every jax.named_scope of the fused decode step: the model's, and sample
SCOPES = (*DECODE_SCOPES, "sample")

# fused-step scan chunk cap: step counts decompose into powers of two up to
# this, so the scan jit compiles at most log2(64)+1 variants ever
MAX_CHUNK = 64


def _chunks(k: int):
    """Decompose k into power-of-two pieces (largest first, capped)."""
    while k > 0:
        c = min(MAX_CHUNK, 1 << (k.bit_length() - 1))
        yield c
        k -= c


def slot_scatter(cache, rows, idx):
    """Write the admitted ``rows`` (leading axis layers, then rows) into
    the cache's slots ``idx``."""
    return jax.tree_util.tree_map(
        lambda full, new: full.at[:, idx].set(new.astype(full.dtype)),
        cache, rows)


@dataclasses.dataclass
class Counters:
    """Work counts since the server was built: monotone host integers,
    none of which waits for the device."""
    prompt_tokens: int = 0         # real prompt tokens admitted
    prefill_tokens: int = 0        # tokens the prefill computed, padding in
    decode_steps: int = 0          # fused decode steps


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    n_new: int = 16


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list
    steps_in_flight: int
    # (len(tokens), V) float32: the logits that chose each token, when the
    # server keeps them (``keep_logits``)
    logits: Optional[np.ndarray] = None


class ContinuousServer:
    def __init__(self, cfg: ModelConfig, *, slots: int = 4, max_seq: int = 128,
                 seed: int = 0, keep_logits: bool = False):
        assert cfg.family in ("dense", "moe", "vlm"), \
            "continuous batching drives the transformer KV-cache layout"
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        # also bring back the logits row that chose each token — the same
        # prefill and scatter, and the fused step with one more output
        self.keep_logits = keep_logits
        self.params = api.build_params(cfg, seed)
        self.cache = api.init_cache(cfg, slots, max_seq)
        # host control plane: deterministic bookkeeping, never syncs device
        self.pos = np.zeros(slots, np.int32)
        self.active = np.zeros(slots, bool)
        self.rid = [-1] * slots
        self.remaining = np.zeros(slots, np.int32)
        self.last_tok = np.zeros(slots, np.int32)
        # device compute state: threaded through the donated fused step
        self._tok_dev = jnp.zeros((slots,), jnp.int32)
        self._pos_dev = jnp.zeros((slots,), jnp.int32)
        self.out: dict[int, list] = {}
        self.out_logits: dict[int, list] = {}
        self.queue: deque[Request] = deque()
        self._done: list[Completion] = []
        self.counters = Counters()

        def admit_prefill(p, t, last_pos, n):
            return api.prefill(p, {"tokens": t}, cfg, cache_len=n,
                               last_pos=last_pos)
        self._prefill = jax.jit(admit_prefill, static_argnames=("n",))
        # one scatter per admission round; the pool-sized cache is donated
        # so XLA writes the admitted rows in place
        self._scatter = jax.jit(slot_scatter, donate_argnums=(0,))
        self._fused = jax.jit(self._fused_impl, donate_argnums=(1, 2, 3),
                              static_argnames=("n_steps", "keep_logits"))

    # ------------------------------------------------------------------
    def _fused_impl(self, params, cache, tok, pos, active, *, n_steps: int,
                    keep_logits: bool = False):
        """n_steps fused decode steps under one jit.  Rows outside
        ``active`` keep their carry frozen (same stale inputs the per-step
        loop fed them), so the token stream is bit-identical to stepping.
        ``keep_logits`` also returns each step's (slots, V) float32 logits."""
        def body(carry, _):
            cache, tok, pos = carry
            logits, cache = api.decode_step(params, cache, tok, pos,
                                            self.cfg)
            logits = pin_logits(logits)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jnp.where(active, nxt, tok)
                pos = jnp.where(active, pos + 1, pos)
            out = (nxt, logits.astype(jnp.float32)) if keep_logits else nxt
            return (cache, tok, pos), out
        (cache, tok, pos), toks = jax.lax.scan(
            body, (cache, tok, pos), None, length=n_steps)
        return cache, tok, pos, toks          # toks: (n_steps, slots)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def n_active(self) -> int:
        """Slots currently holding an in-flight sequence."""
        return int(self.active.sum())

    @property
    def steps(self) -> int:
        """Fused decode steps taken so far (the throughput denominator)."""
        return self.counters.decode_steps

    def prefill_pending(self) -> None:
        """Admit queued requests into free slots (prefill each, copy its
        cache into the slot) without decoding — the calibration driver uses
        this to pin an exact active-slot count before timing ``step()``,
        and tests use it to assert the slot-refill invariants."""
        self._admit()

    # ------------------------------------------------------------------
    def _bucket(self, reqs) -> int:
        """The length a bucketed admission round pads its prompts to."""
        return min(bucket_len(max(len(r.prompt) for r in reqs)),
                   self.max_seq)

    def _prefill_bucketed(self, reqs):
        """ONE batched prefill for the whole admission round: batch padded
        to the slot count, prompts right-padded to a shared power-of-two
        bucket — so the prefill jit compiles once per bucket."""
        m = len(reqs)
        bucket = self._bucket(reqs)
        self.counters.prefill_tokens += self.slots * bucket
        toks = np.zeros((self.slots, bucket), np.int32)
        last = np.zeros((self.slots,), np.int32)
        for j, r in enumerate(reqs):
            toks[j, :len(r.prompt)] = r.prompt
            last[j] = len(r.prompt) - 1
        logits, pc = self._prefill(self.params, jnp.asarray(toks),
                                   jnp.asarray(last), self.max_seq)
        rows = jax.tree_util.tree_map(lambda x: x[:, :m], pc)
        return logits[:m], rows

    def _prefill_exact(self, reqs):
        """Per-request exact-length prefills (MoE/VLM: pad tokens shift
        expert routing, so bucketing would change real tokens).  Caches
        still merge into one per-round scatter."""
        self.counters.prefill_tokens += sum(len(r.prompt) for r in reqs)
        logits, rows = [], []
        for r in reqs:
            lg, pc = self._prefill(
                self.params, jnp.asarray(r.prompt, jnp.int32)[None],
                None, self.max_seq)
            logits.append(lg)
            rows.append(pc)
        if len(rows) == 1:
            return logits[0], rows[0]
        return (jnp.concatenate(logits, axis=0),
                jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=1), *rows))

    def _admit(self):
        free = [s for s in range(self.slots) if not self.active[s]]
        m = min(len(free), len(self.queue))
        if m == 0:
            return
        reqs = [self.queue.popleft() for _ in range(m)]
        idx = free[:m]
        dense = self.cfg.family == "dense"
        self.counters.prompt_tokens += sum(len(r.prompt) for r in reqs)
        # bucket 0: exact-length prefills
        with span("serve.admit", rids=[r.rid for r in reqs],
                  bucket=self._bucket(reqs) if dense else 0):
            with span("serve.admit.prefill"):
                if dense:
                    logits, rows = self._prefill_bucketed(reqs)
                else:
                    logits, rows = self._prefill_exact(reqs)
            # one donated slot-scatter per round, not a pool copy per request
            with span("serve.admit.scatter"):
                self.cache = self._scatter(self.cache, rows,
                                           jnp.asarray(idx, jnp.int32))
            with span("serve.admit.first_token"):
                first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            for j, (s, req) in enumerate(zip(idx, reqs)):
                tok = int(first[j])
                self.active[s] = True
                self.rid[s] = req.rid
                self.pos[s] = len(req.prompt)
                self.remaining[s] = req.n_new - 1
                self.last_tok[s] = tok
                self.out[req.rid] = [tok]
                if self.keep_logits:
                    self.out_logits[req.rid] = [
                        np.asarray(logits[j], np.float32)]
                if req.n_new == 1:
                    self._finish(s)
            # resync the device compute state from the host mirrors (H2D)
            with span("serve.admit.sync"):
                self._tok_dev = jnp.asarray(self.last_tok, jnp.int32)
                self._pos_dev = jnp.asarray(self.pos, jnp.int32)

    def _finish(self, s: int):
        rid = self.rid[s]
        kept = self.out_logits.pop(rid, None)
        self._done.append(Completion(
            rid, list(self.out[rid]), self.counters.decode_steps,
            None if kept is None else np.stack(kept)))
        self.active[s] = False
        self.rid[s] = -1

    # ------------------------------------------------------------------
    def _decode(self, n_steps: int):
        """n_steps fused steps on device, then the (n_steps, slots) token
        block — the single device→host transfer — and, when kept, the
        (n_steps, slots, V) logits, applied to the host control plane."""
        self.counters.decode_steps += n_steps
        with span("serve.decode", steps=n_steps, active=self.n_active()):
            with span("serve.decode.dispatch"):
                self.cache, self._tok_dev, self._pos_dev, out = self._fused(
                    self.params, self.cache, self._tok_dev, self._pos_dev,
                    jnp.asarray(self.active), n_steps=n_steps,
                    keep_logits=self.keep_logits)
            with span("serve.decode.fetch"):
                if self.keep_logits:
                    toks, logits = np.asarray(out[0]), np.asarray(out[1])
                else:
                    toks, logits = np.asarray(out), None
            with span("serve.decode.settle"):
                self._settle(toks, logits)

    def _settle(self, toks: np.ndarray, logits=None):
        """Apply a token block to the host control plane; finish slots
        whose budget (or cache) ran out."""
        for i, row in enumerate(toks):
            for s in range(self.slots):
                if not self.active[s]:
                    continue
                t = int(row[s])
                self.out[self.rid[s]].append(t)
                if logits is not None:
                    self.out_logits[self.rid[s]].append(logits[i, s])
                self.pos[s] += 1
                self.last_tok[s] = t
                self.remaining[s] -= 1
                if self.remaining[s] <= 0 or self.pos[s] >= self.max_seq - 1:
                    self._finish(s)

    def step(self):
        """One fused decode step across all active slots."""
        self._decode(1)

    # ------------------------------------------------------------------
    def run(self) -> list:
        """Drain the queue; returns Completions in finish order.

        Fast path: between admissions, every active slot survives exactly
        ``min(steps-to-finish)`` more steps — so that many are scanned in
        fused chunks with one transfer each, and settlement is pure host
        arithmetic.  Admission points, step counts, and the token streams
        are bit-identical to the per-step loop (pinned in tests)."""
        while self.queue or self.active.any():
            self._admit()
            if not self.active.any():
                continue
            k = min(min(int(self.remaining[s]),
                        self.max_seq - 1 - int(self.pos[s]))
                    for s in range(self.slots) if self.active[s])
            for c in _chunks(max(1, k)):
                self._decode(c)
        done, self._done = self._done, []
        return done

    # ------------------------------------------------------------------
    def compile_stats(self) -> dict:
        """Live jit-cache sizes — the recompile counters the serving bench
        and the bucketing tests assert on."""
        return {"prefill": self._prefill._cache_size(),
                "fused_step": self._fused._cache_size(),
                "scatter": self._scatter._cache_size()}
