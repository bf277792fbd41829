"""RWKV-6 WKV recurrence kernel (TPU Pallas).

The recurrence S_t = diag(w_t) S_{t-1} + k_t (outer) v_t is sequential in t,
but its operands are tiny: the (hd, hd) matrix state lives in VMEM scratch
for the whole sweep while (r,k,v,w) stream through VMEM in CHUNK-step tiles
along the sequential chunk grid axis.  HBM traffic is therefore O(T*hd) in
and O(T*hd) out — the state never round-trips to HBM (the pure-jnp scan
carries it through HBM every step).  Within a chunk the steps run on the
VPU over VMEM-resident tiles.

Layout: every step needs r_t, k_t, w_t as columns (indexed by the state's
row i) and v_t as a row (indexed by its column j):
    o_t[j] = sum_i r_t[i] (S[i, j] + u[i] k_t[i] v_t[j])
    S[i, j] <- w_t[i] S[i, j] + k_t[i] v_t[j]
so r, k, w enter channels-major as (B, H, hd, T) and v, o time-major as
(B, H, T, hd).  Each block's last two dims are then (hd, CHUNK) or
(CHUNK, hd): full head_dim, and a 128-lane multiple or the whole sequence
along time, as the TPU lowering requires.  The chunk's steps are unrolled,
so every column and row slice is static.

Grid: (B, H, T/CHUNK); chunk axis sequential ("arbitrary").
Outputs: per-token o (B,T,H,hd) and the final state (B,H,hd,hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref,
            state, *, chunk: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                      # (hd, 1)
    r = r_ref[0, 0].astype(jnp.float32)                   # (hd, chunk)
    k = k_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)                   # (chunk, hd)
    s = state[...]
    for t in range(chunk):
        kv = k[:, t:t + 1] * v[t:t + 1, :]                # (hd, hd)
        o = jnp.sum((s + u * kv) * r[:, t:t + 1], axis=0, keepdims=True)
        o_ref[0, 0, t:t + 1, :] = o.astype(o_ref.dtype)
        s = w[:, t:t + 1] * s + kv
    state[...] = s

    @pl.when(ci == nc - 1)
    def _finish():
        sout_ref[0, 0] = state[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, s0, *, chunk: int = CHUNK, interpret: bool = False):
    """r,k,v,w: (B,T,H,hd) fp32; u: (H,hd); s0: (B,H,hd,hd).
    Returns (o (B,T,H,hd), final_state (B,H,hd,hd))."""
    b, t, h, hd = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    cols = [jnp.transpose(x, (0, 2, 3, 1)) for x in (r, k, w)]   # (B,H,hd,T)
    rows = jnp.transpose(v, (0, 2, 1, 3))                          # (B,H,T,hd)

    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    col_spec = pl.BlockSpec((1, 1, hd, chunk), lambda bi, hi, ci: (bi, hi, 0, ci))
    row_spec = pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ci: (bi, hi, 0, 0))
    o, sout = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            col_spec, col_spec, row_spec, col_spec,
            pl.BlockSpec((1, hd, 1), lambda bi, hi, ci: (hi, 0, 0)),
            state_spec,
        ],
        out_specs=[row_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cols[0], cols[1], rows, cols[2], u[:, :, None], s0)
    return jnp.swapaxes(o, 1, 2), sout
