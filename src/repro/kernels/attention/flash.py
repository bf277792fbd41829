"""Flash-attention prefill kernel (TPU Pallas).

Fused QK^T -> online-softmax -> PV with causal (+ sliding-window) masking and
GQA head mapping.  VMEM tiling: one (BQ, hd) query tile resident per program;
KV streamed in (BK, hd) tiles along the innermost (sequential) grid axis with
running (m, l, acc) scratch carries — the standard TPU flash schedule with
MXU-aligned 128x128 tiles.

The kernel works on a heads-major (B, H, S, hd) layout so that every block's
last two dims are (sequence tile, head_dim): the TPU lowering needs those to
be (8k, 128k) or the full array dims, which a (1, bq, 1, hd) block over a
(B, S, H, hd) array is not.  ``flash_attention`` takes and returns the
model's (B, S, H, hd) layout and transposes at the boundary.

Grid: (B, H, S/BQ, S/BK), KV axis innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BQ = 128
BK = 128
LANES = 128   # the running max / sum live lane-broadcast in (bq, 128) tiles


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: int, bq: int, bk: int, nk: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q.k in the input dtype (exact products, float32 sums); softmax and
    # p.v in float32
    q = q_ref[0, 0]                                      # (bq, hd)
    k = k_ref[0, 0]                                      # (bk, hd)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, window: int = 0, bq: int = BQ, bk: int = BK,
                    interpret: bool = False):
    """q: (B,S,H,hd); k,v: (B,S,K,hd) with H % K == 0.  Causal (+window)."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = hd ** -0.5
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))   # (B,H,S,hd)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
