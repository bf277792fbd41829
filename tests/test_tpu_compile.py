"""Compile-only checks for the TPU v5e: the main path's kernels at real
widths, and the one-chip serving cut's fused decode step and prefill.

Nothing runs here.  A described (not attached) v5e topology lets the TPU
compiler refuse what the chip would refuse — a block layout the Pallas
lowering does not tile, more VMEM than a kernel may use, a program that
does not fit in HBM — at no chip time.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests and
only the one that runs this file loads the TPU library.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import deepseek_7b, rwkv6_1p6b
from repro.kernels.attention.ops import flash_attention
from repro.kernels.decode.ops import flash_decode
from repro.kernels.rwkv.ops import wkv6
from repro.models import api
from repro.serving.continuous import ContinuousServer

HBM_BYTES = 15.75e9      # what one v5e program may use of the chip's 16 GB
CUT = deepseek_7b.ONE_CHIP
SLOTS, MAX_SEQ = deepseek_7b.ONE_CHIP_SLOTS, deepseek_7b.ONE_CHIP_MAX_SEQ
PROMPT_BUCKET = 512      # largest admission bucket of 100-500-token prompts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # persistent-cache entries written for a described chip cannot be read
    # back without one; keep these compiles out of any cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), tree)


def _hbm(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


# ----------------------------------------------------------------------
# kernels at real widths
# ----------------------------------------------------------------------

def test_flash_attention_compiles_deepseek_widths(one_chip):
    h, hd = CUT.num_heads, CUT.resolved_head_dim
    x = _sds((1, 1024, h, hd), CUT.cdt, one_chip)
    c = jax.jit(flash_attention).lower(x, x, x).compile()
    assert "tpu_custom_call" in c.as_text()


def test_flash_decode_compiles_deepseek_widths(one_chip):
    h, hd = CUT.num_heads, CUT.resolved_head_dim
    q = _sds((SLOTS, 1, h, hd), CUT.cdt, one_chip)
    kv = _sds((SLOTS, MAX_SEQ, CUT.num_kv_heads, hd), CUT.cdt, one_chip)
    valid = _sds((MAX_SEQ,), jnp.bool_, one_chip)
    c = jax.jit(flash_decode).lower(q, kv, kv, valid).compile()
    assert "tpu_custom_call" in c.as_text()


def test_wkv6_compiles_rwkv6_widths(one_chip):
    cfg = rwkv6_1p6b.CONFIG
    h = cfg.num_heads
    hd = cfg.d_model // h
    x = _sds((1, 512, h, hd), jnp.float32, one_chip)
    u = _sds((h, hd), jnp.float32, one_chip)
    s0 = _sds((1, h, hd, hd), jnp.float32, one_chip)
    c = jax.jit(wkv6).lower(x, x, x, x, u, s0).compile()
    assert "tpu_custom_call" in c.as_text()


# ----------------------------------------------------------------------
# the one-chip serving cut: what ContinuousServer compiles
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def cut_state(one_chip):
    params = _on(api.abstract_params(CUT), one_chip)
    cache = _on(api.cache_spec(CUT, SLOTS, MAX_SEQ), one_chip)
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    return params, cache, weights


def test_one_chip_param_build_fits(one_chip):
    """Params built under jit: the float32 draws fuse into the bf16 leaves,
    so the temporaries are far below one float32 layer stack."""
    shardings = jax.tree_util.tree_map(lambda _: one_chip,
                                       api.abstract_params(CUT))
    c = jax.jit(lambda: api.init_params(jax.random.PRNGKey(0), CUT),
                out_shardings=shardings).lower().compile()
    f32_mlp_stack = 4 * CUT.num_layers * CUT.d_model * CUT.d_ff
    assert c.memory_analysis().temp_size_in_bytes < f32_mlp_stack


def test_one_chip_fused_decode_fits(one_chip, cut_state):
    params, cache, weights = cut_state
    srv = ContinuousServer.__new__(ContinuousServer)   # compile, build nothing
    srv.cfg = CUT
    vec = _sds((SLOTS,), jnp.int32, one_chip)
    active = _sds((SLOTS,), jnp.bool_, one_chip)
    fused = jax.jit(srv._fused_impl, donate_argnums=(1, 2, 3),
                    static_argnames=("n_steps",))
    c = fused.lower(params, cache, vec, vec, active, n_steps=8).compile()
    assert weights < _hbm(c) < HBM_BYTES


def test_one_chip_prefill_fits(one_chip, cut_state):
    params, cache, weights = cut_state
    toks = _sds((SLOTS, PROMPT_BUCKET), jnp.int32, one_chip)
    last = _sds((SLOTS,), jnp.int32, one_chip)
    prefill = jax.jit(lambda p, t, lp: api.prefill(
        p, {"tokens": t}, CUT, cache_len=MAX_SEQ, last_pos=lp))
    c = prefill.lower(params, toks, last).compile()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    # the pool-sized cache stays live beside the prefill's program
    assert weights < _hbm(c) + cache_bytes < HBM_BYTES
