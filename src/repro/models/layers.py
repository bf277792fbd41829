"""Transformer building blocks: GQA attention (full / windowed / decode), MLP.

Pure-jnp implementations; the Pallas kernels in ``repro.kernels`` are drop-in
replacements for the hot paths, selected via ``repro.kernels.dispatch``.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from .common import (ModelConfig, activation, apply_norm, apply_rope, dense,
                     dense_init, norm_init)

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------

def attn_init(rng, cfg: ModelConfig) -> dict:
    r = jax.random.split(rng, 4)
    d, pdt = cfg.d_model, cfg.pdt
    return {
        "wq": dense_init(r[0], d, cfg.q_dim, pdt, bias=cfg.qkv_bias),
        "wk": dense_init(r[1], d, cfg.kv_dim, pdt, bias=cfg.qkv_bias),
        "wv": dense_init(r[2], d, cfg.kv_dim, pdt, bias=cfg.qkv_bias),
        "wo": dense_init(r[3], cfg.q_dim, d, pdt),
    }


def _split_heads(x: jnp.ndarray, n: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def causal_window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: int) -> jnp.ndarray:
    """(Sq, Sk) bool mask. window==0 -> plain causal."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def sdpa(q, k, v, mask, *, scale=None):
    """q:(B,Sq,H,hd) k,v:(B,Sk,K,hd) mask:(Sq,Sk) or (B,Sq,Sk) bool.

    Operands stay in their storage dtype (bf16 on TPU) with fp32 MXU
    accumulation via ``preferred_element_type`` — converting the KV cache to
    fp32 would materialise a 2x copy of the largest buffer in the program.
    """
    b, sq, h, hd = q.shape
    kheads = k.shape[2]
    g = h // kheads
    scale = scale if scale is not None else hd ** -0.5
    qf = q.reshape(b, sq, kheads, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qf, k,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


CHUNK_THRESHOLD = 2048   # above this, use the memory-bounded chunked path
Q_CHUNK = 1024


def attention_chunked(q, k, v, q_pos, k_pos, window: int, chunk: int = Q_CHUNK):
    """Memory-bounded attention: scan over query chunks so the logits buffer
    is O(chunk * Sk) — and O(chunk * (chunk + window)) in the windowed case,
    where only the relevant KV band is sliced in.  Same math as ``sdpa``."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    nc = sq // chunk
    qc = q.reshape(b, nc, chunk, h, hd)
    pc = q_pos.reshape(nc, chunk)
    band = min(window + chunk, sk) if window else sk

    def body(_, inp):
        ci, qi, qp = inp
        if window and band < sk:
            start = jnp.clip(ci * chunk + chunk - band, 0, sk - band)
            ks = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kp = start + jnp.arange(band, dtype=jnp.int32)
        else:
            ks, vs, kp = k, v, k_pos
        mask = causal_window_mask(qp, kp, window)
        return None, sdpa(qi, ks, vs, mask)

    idx = jnp.arange(nc, dtype=jnp.int32)
    _, out = jax.lax.scan(body, None, (idx, jnp.moveaxis(qc, 0, 1), pc))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd)


def attention_full(p: dict, x: jnp.ndarray, positions: jnp.ndarray,
                   cfg: ModelConfig, *, window: int | None = None,
                   return_kv: bool = False):
    """Full-sequence (train / prefill) attention.  positions: (S,) int32."""
    win = cfg.attention_window if window is None else window
    s = x.shape[1]
    q = _split_heads(dense(p["wq"], x), cfg.num_heads)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads)
    q = apply_rope(q, positions[None], cfg.rope_theta)
    k = apply_rope(k, positions[None], cfg.rope_theta)
    from repro.kernels import dispatch as _kd
    if _kd.use_pallas("attention"):
        out = _kd.flash_attention(q, k, v, window=win)
    elif s > CHUNK_THRESHOLD and s % Q_CHUNK == 0:
        out = attention_chunked(q, k, v, positions, positions, win)
    else:
        mask = causal_window_mask(positions, positions, win)
        out = sdpa(q, k, v, mask)
    y = dense(p["wo"], out.reshape(*x.shape[:2], -1))
    if return_kv:
        return y, (k, v)
    return y


def attention_decode(p: dict, x: jnp.ndarray, pos: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     cfg: ModelConfig, *, window: int | None = None):
    """Single-token decode.  x: (B,1,d); pos: scalar int32 (current index) or
    (B,) int32 per-sequence positions (continuous batching);
    cache_k/v: (B,S,K,hd) with entries < pos valid.  Returns (y, new_k, new_v).
    """
    win = cfg.attention_window if window is None else window
    b, _, _ = x.shape
    s = cache_k.shape[1]
    q = _split_heads(dense(p["wq"], x), cfg.num_heads)        # (B,1,H,hd)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads)     # (B,1,K,hd)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        # per-sequence positions: rope per row, scatter per row, (B,S) mask
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        rows = jnp.arange(b)
        with jax.named_scope("kv_write"):
            cache_k = cache_k.at[rows, pos].set(k[:, 0].astype(cache_k.dtype))
            cache_v = cache_v.at[rows, pos].set(v[:, 0].astype(cache_v.dtype))
        kv_pos = jnp.arange(s, dtype=jnp.int32)
        valid = kv_pos[None, :] <= pos[:, None]                # (B,S)
        if win:
            valid &= (pos[:, None] - kv_pos[None, :]) < win
        out = sdpa(q, cache_k, cache_v, valid[:, None, :])
        y = dense(p["wo"], out.reshape(b, 1, -1))
        return y, cache_k, cache_v
    posv = jnp.full((1,), 0, jnp.int32) + pos
    q = apply_rope(q, posv[None], cfg.rope_theta)
    k = apply_rope(k, posv[None], cfg.rope_theta)
    with jax.named_scope("kv_write"):
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    # Windowed decode against a much longer cache: slice just the live band so
    # the attention sweep is O(window), not O(S) — this is what makes
    # long_500k viable for the sliding-window dense variants.
    att_k, att_v, base = cache_k, cache_v, jnp.int32(0)
    if win and s > 2 * win:
        base = jnp.clip(pos + 1 - win, 0, s - win)
        att_k = jax.lax.dynamic_slice_in_dim(cache_k, base, win, axis=1)
        att_v = jax.lax.dynamic_slice_in_dim(cache_v, base, win, axis=1)
    kv_pos = base + jnp.arange(att_k.shape[1], dtype=jnp.int32)
    valid = kv_pos <= pos
    if win:
        valid &= (pos - kv_pos) < win
    from repro.kernels import dispatch as _kd
    if _kd.use_pallas("decode"):
        out = _kd.flash_decode(q, att_k, att_v, valid)
    else:
        out = sdpa(q, att_k, att_v, valid[None, None, :])
    y = dense(p["wo"], out.reshape(b, 1, -1))
    return y, cache_k, cache_v


def cross_attention(p: dict, x: jnp.ndarray, enc: jnp.ndarray, cfg: ModelConfig):
    """Encoder-decoder cross attention (no rope, no mask): enc (B,Se,d)."""
    q = _split_heads(dense(p["wq"], x), cfg.num_heads)
    k = _split_heads(dense(p["wk"], enc), cfg.num_kv_heads)
    v = _split_heads(dense(p["wv"], enc), cfg.num_kv_heads)
    mask = jnp.ones((x.shape[1], enc.shape[1]), bool)
    out = sdpa(q, k, v, mask)
    return dense(p["wo"], out.reshape(*x.shape[:2], -1))


# ----------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------

def mlp_init(rng, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    r = jax.random.split(rng, 3)
    d, f, pdt = cfg.d_model, d_ff or cfg.d_ff, cfg.pdt
    return {
        "wi": dense_init(r[0], d, f, pdt),      # gate
        "wu": dense_init(r[1], d, f, pdt),      # up
        "wd": dense_init(r[2], f, d, pdt),      # down
    }


def mlp_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    act = activation(cfg.act)
    return dense(p["wd"], act(dense(p["wi"], x)) * dense(p["wu"], x))


# ----------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------

def embed_init(rng, cfg: ModelConfig) -> dict:
    e = jax.random.normal(rng, (cfg.vocab_size, cfg.d_model), jnp.float32)
    p = {"embedding": (e * cfg.d_model ** -0.5).astype(cfg.pdt)}
    if not cfg.tie_embeddings:
        r2 = jax.random.fold_in(rng, 1)
        p["unembed"] = dense_init(r2, cfg.d_model, cfg.vocab_size, cfg.pdt)
    return p


def embed(p: dict, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return p["embedding"].astype(cfg.cdt)[tokens]


def unembed(p: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return x @ p["embedding"].astype(x.dtype).T
    return dense(p["unembed"], x)
