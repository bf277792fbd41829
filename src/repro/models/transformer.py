"""Decoder-only transformer LM: dense / GQA / QKV-bias / MoE / sliding-window.

Layers are stacked on a leading axis and iterated with ``jax.lax.scan`` so the
lowered HLO is O(1) in depth (essential for 94-layer multi-pod compiles).
Supports three entry points matching the input shapes:
  * ``train_loss``  — full-sequence teacher forcing (train_4k)
  * ``prefill``     — full forward + KV-cache production (prefill_32k)
  * ``decode_step`` — one token against an S-long KV cache (decode_32k / long_500k)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import moe as moe_lib
from .common import ModelConfig, apply_norm, norm_init
from .layers import (attn_init, attention_decode, attention_full, embed,
                     embed_init, mlp_apply, mlp_init, unembed)


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------

def layer_init(rng, cfg: ModelConfig) -> dict:
    r = jax.random.split(rng, 2)
    p = {
        "ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdt),
        "ln2": norm_init(cfg.d_model, cfg.norm, cfg.pdt),
        "attn": attn_init(r[0], cfg),
    }
    if cfg.is_moe:
        p["moe"] = moe_lib.moe_init(r[1], cfg)
    else:
        p["mlp"] = mlp_init(r[1], cfg)
    return p


def init_params(rng, cfg: ModelConfig) -> dict:
    r_embed, r_layers = jax.random.split(rng)
    layers = jax.vmap(lambda r: layer_init(r, cfg))(
        jax.random.split(r_layers, cfg.num_layers))
    return {
        "embed": embed_init(r_embed, cfg),
        "layers": layers,
        "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.pdt),
    }


# ----------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------

def _block(x, lp, positions, cfg: ModelConfig, return_kv: bool):
    from repro import shardctx
    x = shardctx.constrain_batch(x, seq_dim=1)
    h = apply_norm(lp["ln1"], x, cfg.norm)
    if return_kv:
        a, kv = attention_full(lp["attn"], h, positions, cfg, return_kv=True)
    else:
        a = attention_full(lp["attn"], h, positions, cfg)
        kv = None
    x = x + a
    h = apply_norm(lp["ln2"], x, cfg.norm)
    if cfg.is_moe:
        m, aux = moe_lib.moe_apply(lp["moe"], h, cfg)
    else:
        m, aux = mlp_apply(lp["mlp"], h, cfg), jnp.zeros((), jnp.float32)
    return x + m, aux, kv


def forward(params, tokens, cfg: ModelConfig, *, input_embeds=None,
            positions=None, remat: bool = False, return_cache: bool = False):
    """tokens: (B,S) int32 (or input_embeds (B,S,d)).  -> (logits, aux[, kv])."""
    x = embed(params["embed"], tokens, cfg) if input_embeds is None else input_embeds
    x = x.astype(cfg.cdt)
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    def body(carry, lp):
        y, aux, kv = _block(carry, lp, positions, cfg, return_cache)
        ys = (aux, kv) if return_cache else aux
        return y, ys

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, ys = jax.lax.scan(body, x, params["layers"])
    aux = ys[0] if return_cache else ys
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = unembed(params["embed"], x, cfg)
    if return_cache:
        kv = ys[1]  # tuple of (L,B,S,K,hd) stacked k and v
        return logits, jnp.sum(aux), kv
    return logits, jnp.sum(aux)


# ----------------------------------------------------------------------
# loss
# ----------------------------------------------------------------------

def softmax_xent(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - ll).mean()


def train_loss(params, batch, cfg: ModelConfig, *, remat: bool = True):
    logits, aux = forward(params, batch["tokens"], cfg, remat=remat,
                          input_embeds=batch.get("input_embeds"))
    loss = softmax_xent(logits, batch["labels"])
    return loss + aux, {"xent": loss, "aux": aux}


# ----------------------------------------------------------------------
# KV cache + decode
# ----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None) -> dict:
    dt = dtype or cfg.cdt
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, seq, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def cache_spec(cfg: ModelConfig, batch: int, seq: int, dtype=None) -> dict:
    dt = dtype or cfg.cdt
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, seq, cfg.num_kv_heads, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dt),
            "v": jax.ShapeDtypeStruct(shape, dt)}


def prefill(params, tokens, cfg: ModelConfig, cache_len: int | None = None,
            *, input_embeds=None, last_pos=None):
    """Returns (last_logits (B,V), cache dict padded to cache_len).

    ``last_pos`` selects which position's logits count as "last": a scalar
    or (B,) int32 of per-row indices.  Bucketed serving right-pads prompts
    to a shared length, so the real last token sits at ``length - 1``, not
    at ``-1`` — causal masking keeps the logits there identical to an
    exact-length prefill (pad tokens only influence positions after
    themselves, which decode overwrites before they are ever attended)."""
    logits, _aux, (ks, vs) = forward(params, tokens, cfg, return_cache=True,
                                     input_embeds=input_embeds)
    s = ks.shape[2]
    cache_len = cache_len or s
    if cache_len > s:
        pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0), (0, 0)]
        ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    if last_pos is None:
        last = logits[:, -1]
    else:
        last_pos = jnp.asarray(last_pos, jnp.int32)
        if last_pos.ndim == 0:
            last = logits[:, last_pos]
        else:
            last = logits[jnp.arange(logits.shape[0]), last_pos]
    return last, {"k": ks, "v": vs}


# the jax.named_scope names decode_step's operations carry (kv_write is
# attention_decode's cache write, inside attention)
DECODE_SCOPES = ("embed", "norm", "attention", "kv_write", "mlp", "moe",
                 "unembed")


def decode_step(params, cache: dict, token: jnp.ndarray, pos: jnp.ndarray,
                cfg: ModelConfig, *, input_embeds=None):
    """token: (B,) int32; pos: scalar int32.  -> (logits (B,V), new cache).

    Each part runs under a ``jax.named_scope`` (``embed``, ``norm``,
    ``attention``, ``mlp`` or ``moe``, ``unembed``; each sub-layer's
    residual add inside its scope), so a device trace names the layer an
    operation belongs to; what the layer scan adds lies under none."""
    with jax.named_scope("embed"):
        x = (embed(params["embed"], token[:, None], cfg)
             if input_embeds is None else input_embeds)
        x = x.astype(cfg.cdt)

    def body(carry, layer):
        from repro import shardctx
        lp, ck, cv = layer
        carry = shardctx.constrain_batch(carry)
        with jax.named_scope("norm"):
            h = apply_norm(lp["ln1"], carry, cfg.norm)
        with jax.named_scope("attention"):
            a, nk, nv = attention_decode(lp["attn"], h, pos, ck, cv, cfg)
            y = carry + a
        with jax.named_scope("norm"):
            h = apply_norm(lp["ln2"], y, cfg.norm)
        if cfg.is_moe:
            with jax.named_scope("moe"):
                m, _ = moe_lib.moe_apply(lp["moe"], h, cfg)
                y = y + m
        else:
            with jax.named_scope("mlp"):
                y = y + mlp_apply(lp["mlp"], h, cfg)
        return y, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    with jax.named_scope("norm"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
    with jax.named_scope("unembed"):
        logits = unembed(params["embed"], x, cfg)[:, 0]
    return logits, {"k": nk, "v": nv}
