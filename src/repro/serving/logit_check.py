"""Served output checked against the cache-free forward pass.

The served path computes each token through the KV cache: a (bucketed)
prefill, then one decode step per token; ``ContinuousServer`` and
``InferenceEngine.generate`` bring back the logits that chose each token when
asked to keep them.  ``forward`` over the prompt plus the generated tokens
computes every position's logits in one pass, with no cache and no batching
across requests.  The two must agree up to the rounding of the compute
dtype.  Logits are compared, never token ids
alone: with random weights the top two logits can sit within rounding of
each other, so a token is held only to being within a stated logit
tolerance of the forward pass's best.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.common import ModelConfig
from repro.serving.engine import bucket_len


@partial(jax.jit, static_argnames=("cfg",))
def _forward(params, tokens, cfg: ModelConfig):
    logits, _ = api.module_for(cfg).forward(params, tokens, cfg)
    return logits.astype(jnp.float32)


def generated_logits(params, cfg: ModelConfig, prompt, tokens) -> np.ndarray:
    """(len(tokens), V) float32: the cache-free forward pass's logits at the
    positions that chose each generated token (the prompt's last position
    onwards).  A dense model's sequence is right-padded to a power-of-two
    length (causal, so the pad changes no real position); an MoE sequence
    stays exact, since pad tokens would take expert capacity.  The rows are
    sliced on the device, so only those come back."""
    seq = list(prompt) + list(tokens[:-1])
    n = bucket_len(len(seq)) if cfg.family == "dense" else len(seq)
    toks = np.zeros((1, n), np.int32)
    toks[0, :len(seq)] = seq
    logits = _forward(params, jnp.asarray(toks), cfg)
    return np.asarray(logits[0, len(prompt) - 1:len(seq)])


def token_margins(logits: np.ndarray, tokens) -> np.ndarray:
    """How far each chosen token's logit falls below its row's best logit
    (0 where they agree)."""
    score = np.asarray(logits, np.float64)
    picked = score[np.arange(len(tokens)), np.asarray(tokens)]
    return score.max(axis=-1) - picked
