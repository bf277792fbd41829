"""Family dispatch: one uniform API over every architecture family.

Every family module exposes:
    init_params(rng, cfg) -> params
    train_loss(params, batch, cfg, remat=...) -> (loss, metrics)   [not cnn]
    prefill(params, inputs, cfg, cache_len) -> (last_logits, cache)
    decode_step(params, cache, token, pos, cfg) -> (logits, cache)
    init_cache / cache_spec(cfg, batch, seq, dtype)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import cnn, encdec, hybrid, ssm, transformer, vlm
from .common import ModelConfig

_FAMILIES = {
    "dense": transformer,
    "moe": transformer,
    "ssm": ssm,
    "hybrid": hybrid,
    "audio": encdec,
    "vlm": vlm,
    "cnn": cnn,
}


def module_for(cfg: ModelConfig):
    return _FAMILIES[cfg.family]


def init_params(rng, cfg: ModelConfig):
    return module_for(cfg).init_params(rng, cfg)


def build_params(cfg: ModelConfig, seed: int = 0, shardings=None):
    """Seeded params built on the device under one jit, each leaf written
    straight into its target sharding (a pytree of ``Sharding``s; None puts
    everything on the default device).  Traced, the float32 draw, scale and
    cast fuse into the leaf's own dtype, so no float32 copy of a whole
    layer stack is ever materialised — which eager init would do, and which
    at full depth alone can overrun HBM."""
    return jax.jit(lambda: init_params(jax.random.PRNGKey(seed), cfg),
                   out_shardings=shardings)()


def abstract_params(cfg: ModelConfig, seed: int = 0):
    """ShapeDtypeStruct pytree of the params — no allocation (for dry-runs)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(seed), cfg))


def train_loss(params, batch, cfg: ModelConfig, **kw):
    return module_for(cfg).train_loss(params, batch, cfg, **kw)


def prefill(params, inputs, cfg: ModelConfig, cache_len: int | None = None,
            last_pos=None):
    """``last_pos`` (scalar or (B,) int32) selects which position's logits
    to return — the bucketed-prefill hook (right-padded prompts read their
    real last token, not the pad tail).  Only causal-attention families
    support it; MoE routing and recurrent state are length-sensitive, so
    their callers keep exact-length prompts."""
    mod = module_for(cfg)
    if cfg.family in ("audio", "vlm"):
        return mod.prefill(params, inputs, cfg, cache_len)
    if last_pos is not None:
        return mod.prefill(params, inputs["tokens"], cfg, cache_len,
                           last_pos=last_pos)
    return mod.prefill(params, inputs["tokens"], cfg, cache_len)


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    return module_for(cfg).decode_step(params, cache, token, pos, cfg)


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    return module_for(cfg).init_cache(cfg, batch, seq, dtype)


def cache_spec(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    mod = module_for(cfg)
    if hasattr(mod, "cache_spec"):
        spec = mod.cache_spec(cfg, batch, seq, dtype)
        # normalise: some families build from init_cache; force SDS everywhere
        return jax.tree_util.tree_map(
            lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(x.shape, x.dtype), spec)
    return jax.eval_shape(lambda: mod.init_cache(cfg, batch, seq, dtype))
