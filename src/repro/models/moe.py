"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch.

Design notes
------------
The textbook GSPMD MoE (Mesh-TF / T5X) materialises a one-hot dispatch mask of
shape (tokens, E, C) — O(tokens * E * C) memory, which for a 128-expert top-8
layer at 1M train tokens is ~4e13 elements: unusable.  We instead use a
*sort-based* dispatch whose buffers are O(tokens * k * cf * d):

  1. router -> top-k (expert_id, gate) per token,
  2. stable-argsort the (token, choice) pairs by expert id,
  3. position-within-expert = rank - first_rank_of_expert (via searchsorted),
  4. scatter tokens into per-expert capacity buffers (E, C, d), dropping
     overflow (mode='drop'); run the 3 expert matmuls batched over E,
  5. gather back, scale by gate, scatter-add over the k choices.

Tokens are processed in fixed-size *groups* (default 4096) so the capacity C
is bounded and the group axis shards over the data axes; expert weights carry
a leading E axis for expert-parallel sharding over the model axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ModelConfig, activation, dense_init

DEFAULT_GROUP = 4096


def moe_init(rng, cfg: ModelConfig) -> dict:
    r = jax.random.split(rng, 4)
    d, f, e, pdt = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.pdt
    scale = 1.0 / math.sqrt(d)
    return {
        "router": dense_init(r[0], d, e, jnp.float32),
        "wi": (jax.random.normal(r[1], (e, d, f), jnp.float32) * scale).astype(pdt),
        "wu": (jax.random.normal(r[2], (e, d, f), jnp.float32) * scale).astype(pdt),
        "wd": (jax.random.normal(r[3], (e, f, d), jnp.float32) / math.sqrt(f)).astype(pdt),
    }


def capacity(group_size: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(group_size * cfg.num_experts_per_tok
                      * cfg.moe_capacity_factor / cfg.num_experts))
    return max(c, 1)


def _route_group(xg, idx, gate, wi, wu, wd, cfg: ModelConfig, cap: int,
                 e0: int | jnp.ndarray = 0):
    """One group: xg (gs,d), idx/gate (gs,k) -> (gs,d).

    ``wi`` may hold only a local slice of the experts (expert parallelism):
    ``e0`` is this shard's first expert id; choices routed elsewhere are
    dropped here and contributed by the owning shard (combined via psum)."""
    gs, d = xg.shape
    e_loc = wi.shape[0]
    k = cfg.num_experts_per_tok
    act = activation(cfg.act)

    eflat = idx.reshape(-1)                                    # (gs*k,)
    order = jnp.argsort(eflat, stable=True)
    sorted_e = eflat[order]
    ranks = jnp.arange(gs * k, dtype=jnp.int32)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left").astype(jnp.int32)
    pos = ranks - first                    # slot within (global) expert
    tok = (order // k).astype(jnp.int32)
    el = sorted_e - e0                     # local expert index
    valid = (pos < cap) & (el >= 0) & (el < e_loc)
    dest = jnp.where(valid, el * cap + pos, e_loc * cap)       # OOB = dropped

    buf = jnp.zeros((e_loc * cap, d), cfg.cdt)
    buf = buf.at[dest].set(xg.astype(cfg.cdt)[tok], mode="drop")
    buf = buf.reshape(e_loc, cap, d)

    h = act(jnp.einsum("ecd,edf->ecf", buf, wi))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    yb = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap, d)

    gflat = gate.reshape(-1)[order].astype(cfg.cdt) * valid.astype(cfg.cdt)
    contrib = yb[jnp.where(valid, dest, 0)] * gflat[:, None]
    y = jnp.zeros((gs, d), cfg.cdt).at[tok].add(contrib)
    return y


def _dispatch_all_groups(xt, rw, wi, wu, wd, cfg: ModelConfig,
                         group_size: int, e0=0):
    """xt: (T, d) -> (T, d) MoE output (partial when experts are sliced)."""
    t, d = xt.shape
    k = cfg.num_experts_per_tok
    gs = min(t, group_size)
    if t % gs:
        gs = math.gcd(t, gs)
    g = t // gs
    cap = capacity(gs, cfg)
    xg = xt.reshape(g, gs, d)
    logits = xg.astype(jnp.float32) @ rw                       # (G,gs,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    y = jax.vmap(lambda xi, ii, gi: _route_group(
        xi, ii, gi, wi, wu, wd, cfg, cap, e0=e0))(xg, idx, gate)
    return y.reshape(t, d)


def _aux_loss(p, x, cfg: ModelConfig):
    """Switch-style load-balance loss, on the (data-sharded) tokens."""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = x.shape[0] * x.shape[1]
    probs = jax.nn.softmax(
        x.reshape(t, -1).astype(jnp.float32) @ p["router"]["w"], axis=-1)
    _, idx = jax.lax.top_k(probs, k)
    counts = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac_tokens = counts / float(t * k)
    frac_probs = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_weight


def _moe_shard_map(p, x, cfg: ModelConfig, mesh, group_size: int):
    """Explicit-collective MoE over the model axis (see module docstring).

    * EP   (E % model == 0): each shard dispatches only to its E/msz experts,
      one activation-sized psum combines contributions.
    * TP-f (else, d_ff % model == 0): every shard runs the full dispatch with
      an f/msz slice of each expert; the down-proj partials psum the same way.

    Either way the giant (E, C, d) capacity buffers never cross chips — the
    GSPMD-propagated baseline all-reduced them at full size.
    """
    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    msz = mesh.shape["model"]
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dsz = 1
    for a in dp:
        dsz *= mesh.shape[a]
    batch_ok = dp and b % dsz == 0 and dsz > 1
    dspec = (dp if len(dp) > 1 else dp[0]) if batch_ok else None
    xspec = P(dspec, None, None)
    ep = cfg.num_experts % msz == 0

    if ep:
        wspec = {"wi": P("model", None, None), "wu": P("model", None, None),
                 "wd": P("model", None, None)}
    else:
        wspec = {"wi": P(None, None, "model"), "wu": P(None, None, "model"),
                 "wd": P(None, "model", None)}

    def body(xl, rw, wi, wu, wd):
        e0 = jax.lax.axis_index("model") * wi.shape[0] if ep else 0
        bl = xl.shape[0]
        y = _dispatch_all_groups(xl.reshape(bl * s, d), rw, wi, wu, wd,
                                 cfg, group_size, e0=e0)
        return jax.lax.psum(y.reshape(bl, s, d), "model")

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None), wspec["wi"], wspec["wu"], wspec["wd"]),
        out_specs=xspec, check_vma=False)(
        x, p["router"]["w"], p["wi"].astype(cfg.cdt),
        p["wu"].astype(cfg.cdt), p["wd"].astype(cfg.cdt))
    return y.astype(x.dtype), _aux_loss(p, x, cfg)


def moe_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig,
              group_size: int = DEFAULT_GROUP):
    """x: (B, S, d) -> (y, aux_loss).  Uses the explicit shard_map path when
    a mesh with a >1 model axis is installed (repro.shardctx), else the
    single-device dispatch."""
    from repro import shardctx
    mesh = shardctx.get_mesh()
    if (mesh is not None and "model" in getattr(mesh, "axis_names", ())
            and mesh.shape["model"] > 1
            and (cfg.num_experts % mesh.shape["model"] == 0
                 or cfg.d_ff % mesh.shape["model"] == 0)):
        return _moe_shard_map(p, x, cfg, mesh, group_size)

    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    y = _dispatch_all_groups(xt, p["router"]["w"], p["wi"].astype(cfg.cdt),
                             p["wu"].astype(cfg.cdt), p["wd"].astype(cfg.cdt),
                             cfg, group_size)
    return y.reshape(b, s, d).astype(x.dtype), _aux_loss(p, x, cfg)
