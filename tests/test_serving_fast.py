"""Decode fast path: fused-scan/bucketing/scatter parity + recompile pins.

What the fast paths emit is checked against the cache-free ``forward`` pass
over the prompt plus the generated tokens (``repro.serving.logit_check``),
not against token streams recorded on one JAX build.  Those were recorded
with ``jax_threefry_partitionable`` off; JAX 0.9 turns it on by default,
which draws other random weights from the same seed and so changes every
stream from its very first token (with the flag off again, every recorded
engine, continuous and MoE stream is reproduced exactly).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS
from repro.models import api
from repro.serving import logit_check
from repro.serving.continuous import ContinuousServer, Request, _chunks
from repro.serving.engine import InferenceEngine, bucket_len
from repro.serving.kvcache import PagedPool
from repro.serving.sampler import sample_token

CFG = ARCHS["deepseek-7b"].smoke
MOE = ARCHS["granite-moe-3b-a800m"].smoke

# Logit tolerance for float32 smoke configs.  The cached and cache-free
# paths differ only in float32 rounding (about 3e-6 here, logits of std ~1);
# bfloat16 arithmetic anywhere on the path would move them by ~1e-2.
TOL = 1e-4

# scheduling facts of the 7-request, 3-slot, n_new=5 run below: they follow
# from the prompt lengths and budgets alone, not from token values
CONT_STEPS = 12
CONT_ORDER = [0, 1, 2, 3, 4, 5, 6]
CONT_IN_FLIGHT = [4, 4, 4, 8, 8, 8, 12]


def _assert_matches_forward(params, cfg, prompt, tokens, noise=0.0,
                            temperature=1.0):
    """Every emitted token is the forward pass's best (plus the sampling
    noise, when sampled) to within TOL."""
    logits = logit_check.generated_logits(params, cfg, prompt, tokens)
    m = logit_check.token_margins(logits / temperature + noise, tokens)
    assert m.max() <= TOL / temperature, (tokens, m)


def _cont_requests(n, seed=0, n_new=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, CFG.vocab_size,
                                        size=int(rng.integers(4, 12))).tolist(),
                    n_new=n_new)
            for i in range(n)]


# ----------------------------------------------------------------------
# engine: fused scan
# ----------------------------------------------------------------------

def test_engine_scan_matches_pre_fast_path_golden():
    """The fused scan's greedy and sampled streams agree with the forward
    pass; a sampled token is held to the same rule after adding the Gumbel
    draw of the key that ``generate`` used at that step."""
    eng = InferenceEngine(CFG, seed=0, max_cache=48)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    res = eng.generate(jnp.asarray([prompt], jnp.int32), 6)
    _assert_matches_forward(eng.params, CFG, prompt,
                            [int(t) for t in np.asarray(res.tokens[0])])
    temp = 0.8
    res_t = eng.generate(jnp.asarray([prompt], jnp.int32), 6,
                         temperature=temp, seed=7)
    rng = jax.random.PRNGKey(7)
    keys = [rng]                    # first token: the seed key itself
    for _ in range(5):              # then one split per scanned step
        rng, sub = jax.random.split(rng)
        keys.append(sub)
    noise = np.stack([np.asarray(jax.random.gumbel(
        k, (1, CFG.vocab_size), jnp.float32))[0] for k in keys])
    _assert_matches_forward(eng.params, CFG, prompt,
                            [int(t) for t in np.asarray(res_t.tokens[0])],
                            noise=noise, temperature=temp)


def test_engine_scan_matches_stream_loop():
    """The fused scan and the per-token stream loop must emit identical
    tokens — greedy and sampled (the RNG key sequence is replicated)."""
    eng = InferenceEngine(CFG, seed=0, max_cache=64)
    prompt = jnp.asarray([[7, 7, 2, 9, 1], [5, 0, 3, 3, 8]], jnp.int32)
    for temp, seed in ((0.0, 0), (0.9, 11)):
        fused = eng.generate(prompt, 9, temperature=temp, seed=seed)
        stream = eng.generate_stream(prompt, 9, temperature=temp, seed=seed)
        np.testing.assert_array_equal(np.asarray(fused.tokens),
                                      np.asarray(stream.tokens))
    assert stream.token_walls is not None and len(stream.token_walls) == 8
    assert fused.token_walls is None


def test_engine_bucketing_hits_compile_cache():
    """Prompt lengths 5/6/7 share the len-8 bucket: one prefill compile,
    and a shared n_new means one scan compile."""
    eng = InferenceEngine(CFG, seed=0, max_cache=32)
    for s in (5, 6, 7):
        eng.generate(jnp.asarray([[1] * s], jnp.int32), 4)
    stats = eng.compile_stats()
    assert stats["prefill"] == 1
    assert stats["decode_scan"] == 1
    # a new bucket costs exactly one more prefill compile
    eng.generate(jnp.asarray([[1] * 12], jnp.int32), 4)
    assert eng.compile_stats()["prefill"] == 2


def test_bucketed_prefill_last_logits_bit_exact():
    """Right-padding a dense prompt to its bucket and reading logits at
    ``len-1`` gives the exact-length prefill's logits (causal masking: pad
    tokens only influence positions after themselves).  Equal up to float32
    rounding, not bit for bit: the padded matmuls have other shapes, so XLA
    may sum in another order (observed difference ~1e-6)."""
    params = api.init_params(jax.random.PRNGKey(0), CFG)
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6, 5, 3]], jnp.int32)  # s=10
    s = prompt.shape[1]
    exact, _ = api.prefill(params, {"tokens": prompt}, CFG, cache_len=32)
    padded = jnp.pad(prompt, [(0, 0), (0, bucket_len(s) - s)])
    bucketed, _ = api.prefill(params, {"tokens": padded}, CFG, cache_len=32,
                              last_pos=jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(exact), np.asarray(bucketed),
                               rtol=0, atol=TOL)


# ----------------------------------------------------------------------
# continuous server: fused chunks + batched admission
# ----------------------------------------------------------------------

def test_continuous_matches_pre_fast_path_golden():
    reqs = _cont_requests(7)
    srv = ContinuousServer(CFG, slots=3, max_seq=48, seed=0)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    for c in done:
        assert len(c.tokens) == 5
        _assert_matches_forward(srv.params, CFG, reqs[c.rid].prompt, c.tokens)
    assert srv.steps == CONT_STEPS
    assert [c.rid for c in done] == CONT_ORDER
    assert [c.steps_in_flight for c in done] == CONT_IN_FLIGHT


def _moe_requests():
    rng = np.random.default_rng(3)
    return [Request(rid=i,
                    prompt=rng.integers(0, MOE.vocab_size,
                                        size=int(rng.integers(3, 8))).tolist(),
                    n_new=4)
            for i in range(4)]


def _moe_served_vs_single(srv):
    """Serve the MoE requests through ``srv``; return the largest logit
    difference from one-request, exact-length ``InferenceEngine`` runs, and
    whether every token agrees.

    At the served capacity factor (1.25) an exact-length prefill drops
    tokens, so routing depends on which tokens share a group: a forward pass
    over prompt plus output is no reference past the first token.  A
    one-request run groups the prefill exactly as the server must, and a
    decode step of one or two rows cannot overflow an expert (each row picks
    2 distinct experts of 4, capacity >= 1 per row), so the two must agree
    everywhere.  The first token is also held to ``forward`` over exactly
    its prompt, which the prefill groups the same way."""
    reqs = _moe_requests()
    for r in reqs:
        srv.submit(r)
    done = {c.rid: c for c in srv.run()}
    assert sorted(done) == [0, 1, 2, 3]
    eng = InferenceEngine(MOE, seed=0, max_cache=srv.max_seq)
    worst, same = 0.0, True
    for r in reqs:
        c = done[r.rid]
        assert len(c.tokens) == c.logits.shape[0] == 4
        one = eng.generate(jnp.asarray([r.prompt], jnp.int32), r.n_new,
                           keep_logits=True)
        first = logit_check.generated_logits(srv.params, MOE, r.prompt,
                                             c.tokens[:1])
        worst = max(worst,
                    float(np.abs(c.logits - np.asarray(one.logits[0])).max()),
                    float(np.abs(c.logits[:1] - first).max()))
        same &= c.tokens == [int(t) for t in np.asarray(one.tokens[0])]
    return worst, same


def test_continuous_moe_matches_golden():
    """MoE through exact-length prefills and the fused decode, at the served
    capacity factor, agrees with one request served alone."""
    assert MOE.moe_capacity_factor == 1.25
    srv = ContinuousServer(MOE, slots=2, max_seq=24, seed=0,
                           keep_logits=True)
    worst, same = _moe_served_vs_single(srv)
    assert worst <= TOL and same


def test_continuous_moe_bucketed_prefill_is_caught():
    """The check above has teeth: a server that padded MoE prompts to a
    shared bucket (as it does dense ones) lets pad tokens and the other
    request take expert capacity, and misses it by far more than TOL."""
    srv = ContinuousServer(MOE, slots=2, max_seq=24, seed=0,
                           keep_logits=True)
    srv._prefill_exact = srv._prefill_bucketed
    worst, _ = _moe_served_vs_single(srv)
    assert worst > 1e3 * TOL


def test_continuous_fused_matches_per_step():
    """run() (fused multi-step chunks) and a manual step() loop must emit
    identical streams — the chunk length never crosses a finish/admit."""
    reqs = _cont_requests(6, seed=42, n_new=7)
    fast = ContinuousServer(CFG, slots=3, max_seq=48, seed=0)
    slow = ContinuousServer(CFG, slots=3, max_seq=48, seed=0)
    for r in reqs:
        fast.submit(r)
        slow.submit(Request(r.rid, list(r.prompt), r.n_new))
    fast_done = {c.rid: c.tokens for c in fast.run()}
    while slow.queue or slow.active.any():
        slow.prefill_pending()
        if slow.active.any():
            slow.step()
    slow_done = {c.rid: c.tokens for c in slow._done}
    assert fast_done == slow_done
    assert fast.steps == slow.steps


def test_continuous_admission_compile_reuse():
    """Mixed prompt lengths within one bucket reuse the prefill compile;
    fused chunks compile once per power-of-two length."""
    srv = ContinuousServer(CFG, slots=4, max_seq=64, seed=0)
    for i in range(4):
        srv.submit(Request(rid=i, prompt=[1 + i] * (5 + i), n_new=4))
    srv.run()
    first = srv.compile_stats()
    assert first["prefill"] == 1               # lengths 5-8 share bucket 8
    for i in range(4):
        srv.submit(Request(rid=10 + i, prompt=[2 + i] * (5 + i), n_new=4))
    srv.run()
    assert srv.compile_stats() == first        # second round: zero compiles


def test_chunk_decomposition():
    assert list(_chunks(1)) == [1]
    assert list(_chunks(7)) == [4, 2, 1]
    assert list(_chunks(64)) == [64]
    assert list(_chunks(200)) == [64, 64, 64, 8]
    assert sum(_chunks(1337)) == 1337


# ----------------------------------------------------------------------
# paged pool: scatter vs reference loop
# ----------------------------------------------------------------------

def test_pool_scatter_matches_reference_loop():
    cfg = CFG
    l, kh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    pool = PagedPool(cfg, n_blocks=8, block=4, dtype="float32")
    s = 10
    ks = jax.random.normal(jax.random.PRNGKey(0), (l, s, kh, hd))
    vs = jax.random.normal(jax.random.PRNGKey(1), (l, s, kh, hd))
    pool.allocate(7, s)
    pool.write_prefill(7, ks, vs)

    # reference: the old per-block loop semantics
    ref = jnp.zeros_like(pool.k)
    for j, b in enumerate(pool.tables[7]):
        lo, hi = j * pool.block, min((j + 1) * pool.block, s)
        if lo >= s:
            break
        chunk = ks[:, lo:hi]
        if hi - lo < pool.block:
            chunk = jnp.pad(chunk,
                            [(0, 0), (0, pool.block - (hi - lo)),
                             (0, 0), (0, 0)])
        ref = ref.at[:, b].set(chunk)
    np.testing.assert_array_equal(np.asarray(pool.k), np.asarray(ref))

    gk, gv, mask = pool.gather(7)
    assert int(mask.sum()) == s
    np.testing.assert_array_equal(np.asarray(gk[:, :s]), np.asarray(ks))
    np.testing.assert_array_equal(np.asarray(gv[:, :s]), np.asarray(vs))

    pool.extend(7)
    tok = jax.random.normal(jax.random.PRNGKey(2), (l, kh, hd))
    pool.write_token(7, tok, tok)
    gk, _, mask = pool.gather(7)
    assert int(mask.sum()) == s + 1
    np.testing.assert_array_equal(np.asarray(gk[:, s]), np.asarray(tok))


# ----------------------------------------------------------------------
# satellites: sampler top-k, batcher per-request budgets
# ----------------------------------------------------------------------

def test_top_k_matches_full_sort_reference():
    logits = jax.random.normal(jax.random.PRNGKey(5), (4, 257))
    rng = jax.random.PRNGKey(9)
    for k in (1, 5, 64):
        got = sample_token(logits, 0.7, rng, top_k=k)
        # reference: the old full-vocab sort masking
        l = logits.astype(jnp.float32) / 0.7
        kth = jnp.sort(l, axis=-1)[:, -k][:, None]
        ref = jax.random.categorical(
            rng, jnp.where(l < kth, -jnp.inf, l), axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_batcher_per_request_budgets():
    from repro.serving.batcher import Batcher, PendingRequest
    b = Batcher(max_batch=4, max_wait_s=0.0)
    asks = [2, 16, 5, 9]
    for i, n in enumerate(asks):
        b.submit(PendingRequest(rid=i, tokens=[1] * (3 + i), arrival_s=0.0,
                                n_new=n))
    batch = b.form_batch(1.0)
    assert batch.n_new == 16               # decode budget: the batch max
    assert batch.n_new_each == asks        # settlement trims to these
    eng = InferenceEngine(CFG, seed=0, max_cache=32)
    res = eng.generate(jnp.asarray(batch.tokens), batch.n_new)
    outs = {rid: np.asarray(res.tokens[i, :batch.n_new_each[i]])
            for i, rid in enumerate(batch.rids)}
    for i, n in enumerate(asks):
        assert outs[i].shape == (n,)       # nobody billed for the batch max


def test_cached_logits_match_forward():
    """The logits the server chose each token from, through bucketed
    admission, the slot scatter and the fused chunks, equal the forward
    pass's at every generated position, for requests of different lengths
    sharing the slots; keeping them changes no token."""
    reqs = _cont_requests(5, seed=5, n_new=6)
    kept = ContinuousServer(CFG, slots=3, max_seq=32, seed=0,
                            keep_logits=True)
    plain = ContinuousServer(CFG, slots=3, max_seq=32, seed=0)
    for r in reqs:
        kept.submit(r)
        plain.submit(Request(r.rid, list(r.prompt), r.n_new))
    done = {c.rid: c for c in kept.run()}
    plain_done = plain.run()
    assert {c.rid: c.tokens for c in plain_done} == \
        {rid: c.tokens for rid, c in done.items()}
    assert all(c.logits is None for c in plain_done)
    for r in reqs:
        c = done[r.rid]
        ref = logit_check.generated_logits(kept.params, CFG, r.prompt,
                                           c.tokens)
        np.testing.assert_allclose(c.logits, ref, rtol=0, atol=TOL)
