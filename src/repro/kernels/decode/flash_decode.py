"""Flash-decode kernel (TPU Pallas): one new token against a long KV cache.

Decode attention is an HBM-bandwidth sweep over the cache (decode_32k /
long_500k are memory-bound in the roofline table); this kernel streams the
cache in (BK, hd) VMEM tiles along a sequential grid axis, keeping the
online-softmax partials (m, l, acc) in VMEM scratch — the two-pass combine
collapses into one pass because the query is a single row per head.

Layout: the cache keeps the model's (B, S, K, hd) layout and is viewed as
(B, S, K*hd) — a free reshape, so no per-step copy of the largest buffer in
the program.  A program owns ``hb`` KV heads (the fewest whose columns make a
128-lane multiple, one head when hd is 128) and all ``g`` query heads that
share each of them, so every block's last two dims satisfy the TPU tiling
rule: (bk, hb*hd) for the cache and (g, hd) for the query.

A validity vector masks ring-buffer slots / positions beyond `pos` (the
caller encodes causal + window validity there).

Grid: (B, K/hb, S/BK), KV axis innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BK = 512
LANES = 128


def heads_per_block(kv_heads: int, hd: int) -> int:
    """Fewest KV heads whose concatenated columns are a 128-lane multiple
    (or all of them)."""
    for hb in range(1, kv_heads + 1):
        if kv_heads % hb == 0 and (hb * hd) % LANES == 0:
            return hb
    return kv_heads


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, nk: int, hb: int, hd: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q.k in the input dtype (exact products, float32 sums); softmax and
    # p.v in float32
    valid = valid_ref[...] != 0                          # (1, bk)
    for j in range(hb):                                  # static: hb is small
        q = q_ref[0, j]                                  # (g, hd)
        k = k_ref[0, :, j * hd:(j + 1) * hd]             # (bk, hd)
        v = v_ref[0, :, j * hd:(j + 1) * hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                 # (g, bk)
        m_prev = m_ref[j][:, :1]                         # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[j][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[j] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[j] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(ki == nk - 1)
    def _finish():
        for j in range(hb):
            l = jnp.maximum(l_ref[j][:, :1], 1e-30)
            o_ref[0, j] = (acc_ref[j] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q, cache_k, cache_v, valid, *, bk: int = BK,
                 interpret: bool = False):
    """q: (B,1,H,hd); cache_k/v: (B,S,K,hd); valid: (S,) bool."""
    b, _, h, hd = q.shape
    s, kh = cache_k.shape[1], cache_k.shape[2]
    g = h // kh
    hb = heads_per_block(kh, hd)
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    nk = s // bk
    scale = hd ** -0.5
    qg = q.reshape(b, kh, g, hd)                         # query heads by KV head
    ck = cache_k.reshape(b, s, kh * hd)
    cv = cache_v.reshape(b, s, kh * hd)
    valid2 = valid[None, :].astype(jnp.int32)            # (1, S) blockable

    kernel = functools.partial(_kernel, scale=scale, nk=nk, hb=hb, hd=hd)
    out = pl.pallas_call(
        kernel,
        grid=(b, kh // hb, nk),
        in_specs=[
            pl.BlockSpec((1, hb, g, hd), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bk, hb * hd), lambda bi, hi, ki: (bi, ki, hi)),
            pl.BlockSpec((1, bk, hb * hd), lambda bi, hi, ki: (bi, ki, hi)),
            pl.BlockSpec((1, bk), lambda bi, hi, ki: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, hb, g, hd), lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hb, g, LANES), jnp.float32),
            pltpu.VMEM((hb, g, LANES), jnp.float32),
            pltpu.VMEM((hb, g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, ck, cv, valid2)
    return out.reshape(b, 1, h, hd)
