"""Tests for the beyond-paper extensions: quantization, autoscaler,
workloads, shardctx, launchers' building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.autoscaler import Autoscaler, concurrency_profile
from repro.core.workload import cold_probe, poisson, step_ramp, warm_burst
from repro.models import api
from repro.serving.quantize import (dequantize_params, quantization_error,
                                    quantize_params)

RNG = jax.random.PRNGKey(0)


# ------------------------------------------------------------ quantize
def test_quantize_halves_weight_bytes():
    cfg = ARCHS["deepseek-7b"].smoke.replace(param_dtype="bfloat16")
    params = api.init_params(RNG, cfg)
    _, stats = quantize_params(params)
    assert stats["quantized_leaves"] > 4
    assert stats["ratio"] < 0.62          # ~0.5 + scales + norms


def test_quantize_roundtrip_small_error():
    cfg = ARCHS["deepseek-7b"].smoke
    params = api.init_params(RNG, cfg)
    assert quantization_error(params) < 0.02


def test_quantized_model_logits_close():
    cfg = ARCHS["deepseek-7b"].smoke
    params = api.init_params(RNG, cfg)
    toks = jax.random.randint(RNG, (2, 16), 0, cfg.vocab_size)
    mod = api.module_for(cfg)
    ref, _ = mod.forward(params, toks, cfg)
    qt, _ = quantize_params(params)
    deq = dequantize_params(qt, dtype=jnp.float32)
    got, _ = mod.forward(deq, toks, cfg)
    # int8 weight-only: top-1 predictions should essentially agree
    agree = jnp.mean((jnp.argmax(ref, -1) == jnp.argmax(got, -1))
                     .astype(jnp.float32))
    assert float(agree) > 0.9


# ------------------------------------------------------------ workloads
def test_workloads_are_deterministic_and_ordered():
    for wl in (cold_probe(), warm_burst(), step_ramp(), poisson(2.0, 10.0)):
        times = [r.arrival_s for r in wl]
        assert times == sorted(times)
    a = poisson(3.0, 20.0, seed=5)
    b = poisson(3.0, 20.0, seed=5)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]


def test_step_ramp_matches_fig7():
    per_sec = {}
    for r in step_ramp():
        per_sec[int(r.arrival_s)] = per_sec.get(int(r.arrival_s), 0) + 1
    assert [per_sec[s] for s in sorted(per_sec)] == list(range(10, 101, 10))


# ------------------------------------------------------------ autoscaler
def test_concurrency_profile_counts_inflight():
    from repro.core.function import FunctionSpec, Handler
    from repro.core.simulator import Simulator
    spec = FunctionSpec(Handler(name="x", base_cpu_seconds=0.5), 1024)
    recs = Simulator(spec, seed=0).run(step_ramp(10, 0, 2))
    prof = concurrency_profile(recs)
    assert prof["peak_inflight"] >= 5
    assert prof["containers"] == len({r.container_id for r in recs})


def test_autoscaler_pool_scales_with_rate():
    a = Autoscaler(window_s=5.0, margin=1.5)
    arrivals = [i * 0.1 for i in range(100)]   # 10 rps
    low = a.desired_pool(arrivals[:10], now=1.0, service_time_s=0.5)
    high = a.desired_pool(arrivals, now=9.9, service_time_s=0.5)
    assert high >= low


# ------------------------------------------------------------ shardctx
def test_shardctx_noop_without_mesh():
    from repro import shardctx
    x = jnp.ones((4, 8))
    assert shardctx.constrain_batch(x) is x


def test_shardctx_constrains_with_mesh():
    from repro import shardctx
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    with shardctx.use_mesh(mesh):
        x = jnp.ones((4, 8))
        y = shardctx.constrain_batch(x)          # axis size 1: no constraint
        assert y is x or y.shape == x.shape


# ------------------------------------------------------------ hlo parser
def test_hlo_parser_ignores_done_ops_and_metadata_text():
    from repro.analysis import hlo
    txt = """
ENTRY %m (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %ar = f32[8] all-reduce-start(%p), replica_groups=[2,4]<=[8]
  %d = f32[8] all-reduce-done(%ar)
  ROOT %r = f32[8] add(%d, %d), metadata={op_name="fake/all-to-all/x"}
}
"""
    coll = hlo.collective_bytes(txt)
    assert coll["counts"] == {"all-reduce": 1}   # -start once, -done ignored


def test_hlo_parser_group_size_formats():
    from repro.analysis.hlo import _group_size
    assert _group_size("replica_groups=[4,16]<=[64]") == 16
    assert _group_size("replica_groups={{0,1,2,3}}") == 4


# ------------------------------------------------------------ registry
def test_registry_covers_assignment_matrix():
    from repro.configs.base import SHAPES
    from repro.configs.registry import ARCHS, input_specs, pairs
    assert len(ARCHS) == 10
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    ps = pairs()
    assert len(ps) == 39  # 40 - whisper long_500k
    # every pair produces lowered-compatible specs without allocation
    for aid, sid in ps:
        kind, cfg, kw = input_specs(aid, sid)
        leaves = jax.tree_util.tree_leaves(kw)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        if sid == "long_500k":
            assert cfg.family in ("ssm", "hybrid") or cfg.attention_window > 0


def test_exact_assigned_configs():
    """Pin the exact assignment table values."""
    a = ARCHS
    c = a["rwkv6-1.6b"].config
    assert (c.num_layers, c.d_model, c.d_ff, c.vocab_size) == (24, 2048, 7168, 65536)
    c = a["recurrentgemma-9b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (38, 4096, 16, 1, 12288, 256000)
    c = a["whisper-tiny"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.d_ff,
            c.vocab_size) == (4, 384, 6, 1536, 51865)
    c = a["llava-next-mistral-7b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (32, 4096, 32, 8, 14336, 32000)
    c = a["deepseek-7b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (30, 4096, 32, 32, 11008, 102400)
    c = a["granite-moe-3b-a800m"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.num_experts, c.num_experts_per_tok) == \
        (32, 1536, 24, 8, 512, 49155, 40, 8)
    c = a["qwen2.5-32b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (64, 5120, 40, 8, 27648, 152064, True)
    c = a["qwen3-moe-235b-a22b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.num_experts, c.num_experts_per_tok) == \
        (94, 4096, 64, 4, 1536, 151936, 128, 8)
    c = a["qwen1.5-110b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (80, 8192, 64, 8, 49152, 152064, True)
    c = a["mistral-nemo-12b"].config
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (40, 5120, 32, 8, 14336, 131072)
