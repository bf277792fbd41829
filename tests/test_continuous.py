"""Continuous batching: slot scheduling + exactness vs individual decoding,
and the server's own spans, scopes and counters."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.serving.continuous import SCOPES, ContinuousServer, Request
from repro.serving.engine import InferenceEngine

CFG = ARCHS["deepseek-7b"].smoke


def _requests(n, seed=0, n_new=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, CFG.vocab_size,
                                        size=int(rng.integers(4, 12))).tolist(),
                    n_new=n_new)
            for i in range(n)]


def test_continuous_matches_individual_greedy():
    reqs = _requests(7)
    srv = ContinuousServer(CFG, slots=3, max_seq=48, seed=0)
    for r in reqs:
        srv.submit(r)
    done = {c.rid: c.tokens for c in srv.run()}
    assert sorted(done) == list(range(7))
    eng = InferenceEngine(CFG, seed=0, max_cache=48)
    for r in reqs:
        res = eng.generate(jnp.asarray(r.prompt, jnp.int32)[None], r.n_new)
        assert [int(t) for t in np.asarray(res.tokens[0])] == done[r.rid]


def test_continuous_fuses_decode_steps():
    """7 x 5-token requests on 3 slots must need far fewer fused steps than
    sequential serving (7*4 decode steps) — that's the throughput win."""
    reqs = _requests(7)
    srv = ContinuousServer(CFG, slots=3, max_seq=48, seed=0)
    for r in reqs:
        srv.submit(r)
    srv.run()
    assert srv.steps <= 14             # ceil(7*4 / 3) + admission skew
    assert srv.steps < 7 * 4


def test_slot_reuse_and_varied_lengths():
    reqs = [Request(0, [1, 2, 3], n_new=2), Request(1, [4, 5], n_new=8),
            Request(2, [6], n_new=1), Request(3, [7, 8, 9, 10], n_new=4)]
    srv = ContinuousServer(CFG, slots=2, max_seq=32, seed=0)
    for r in reqs:
        srv.submit(r)
    done = {c.rid: c.tokens for c in srv.run()}
    for r in reqs:
        assert len(done[r.rid]) == r.n_new


def test_rejects_non_transformer_family():
    with pytest.raises(AssertionError):
        ContinuousServer(ARCHS["rwkv6-1.6b"].smoke, slots=2, max_seq=16)


def _counted_run(cfg):
    """Three requests on 2 slots, worked out by hand: round one admits A
    (3 prompt tokens, 3 new) and B (5, 5) at bucket 8, and decodes 2 steps
    (A finishes); round two admits C (9, 2) at bucket 16, and decodes 1
    step (C finishes); B decodes 1 step more alone."""
    srv = ContinuousServer(cfg, slots=2, max_seq=32, seed=0)
    for rid, (n, n_new) in enumerate([(3, 3), (5, 5), (9, 2)]):
        srv.submit(Request(rid, list(range(1, n + 1)), n_new=n_new))
    done = {c.rid: c.tokens for c in srv.run()}
    assert {r: len(t) for r, t in done.items()} == {0: 3, 1: 5, 2: 2}
    return srv


@pytest.mark.parametrize("cfg,prefill_tokens", [
    (CFG, 2 * 8 + 2 * 16),                       # every slot x the bucket
    (ARCHS["granite-moe-3b-a800m"].smoke, 17),   # exact lengths
], ids=["bucketed", "moe_exact"])
def test_counters_count_a_known_run(cfg, prefill_tokens):
    srv = _counted_run(cfg)
    c = srv.counters
    assert (c.prompt_tokens, c.prefill_tokens) == (17, prefill_tokens)
    assert c.decode_steps == 4
    assert srv.steps == c.decode_steps


def test_counters_follow_step_and_prefill_pending():
    """The online entries count as ``run`` does: one admission round of
    two prompts into three slots, then one decode step."""
    srv = ContinuousServer(CFG, slots=3, max_seq=32, seed=0)
    srv.submit(Request(0, [1, 2, 3], n_new=4))
    srv.submit(Request(1, [4, 5, 6, 7, 8], n_new=4))
    srv.prefill_pending()
    srv.step()
    c = srv.counters
    assert (c.prompt_tokens, c.prefill_tokens) == (8, 3 * 8)
    assert c.decode_steps == srv.steps == 1


def _trace_events(directory):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb")
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]


def test_traced_run_holds_every_serve_span(tmp_path):
    srv = ContinuousServer(CFG, slots=2, max_seq=32, seed=0)
    srv.submit(Request(7, [1, 2, 3], n_new=3))
    srv.run()                                   # compile outside the trace
    srv.submit(Request(8, [4, 5, 6], n_new=3))
    srv.submit(Request(9, [7, 8, 9, 10, 11], n_new=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.run()
    finally:
        jax.profiler.stop_trace()
    events = _trace_events(tmp_path)
    names = {n for n, _ in events if n.startswith("serve.")}
    assert names == {
        "serve.admit", "serve.admit.prefill", "serve.admit.scatter",
        "serve.admit.first_token", "serve.admit.sync", "serve.decode",
        "serve.decode.dispatch", "serve.decode.fetch", "serve.decode.settle"}
    admit = [s for n, s in events if n == "serve.admit"]
    assert admit == [{"rids": "[8, 9]", "bucket": 8}]
    decode = [s for n, s in events if n == "serve.decode"]
    assert decode == [{"steps": 2, "active": 2}]


def test_fused_step_hlo_carries_the_scopes():
    """The compiled decode step's operations name their layer under every
    scope the server exports (the dense model has no ``moe``), the cache
    write inside attention; the admission programs carry their own
    names."""
    srv = ContinuousServer(CFG, slots=2, max_seq=32, seed=0)
    vec = jnp.zeros((2,), jnp.int32)
    text = srv._fused.lower(srv.params, srv.cache, vec, vec,
                            jnp.ones((2,), bool),
                            n_steps=1).compile().as_text()
    for scope in SCOPES:
        if scope != "moe":
            assert f"/{scope}/" in text, scope
    assert "/attention/kv_write/" in text
    toks = jnp.zeros((2, 8), jnp.int32)
    prefill = srv._prefill.lower(srv.params, toks, vec, 32)
    _, rows = jax.eval_shape(srv._prefill, srv.params, toks, vec, 32)
    scatter = srv._scatter.lower(srv.cache, rows, vec)
    assert prefill.as_text().startswith("module @jit_admit_prefill")
    assert scatter.as_text().startswith("module @jit_slot_scatter")
