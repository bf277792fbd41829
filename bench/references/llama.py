"""The plain reference: a Llama-style decoder in float32, and its control.

It follows the published definition that DeepSeek LLM 7B (arXiv:2401.02954,
section 2: the LLaMA architecture) and Mistral-Nemo (``MistralForCausalLM``)
share: pre-norm RMSNorm with the configuration's epsilon, rotary embeddings
that rotate the two halves of each head with the configuration's theta,
causal grouped-query attention scaled by ``head_dim ** -0.5``, a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), a final RMSNorm and an untied vocabulary
projection.  Everything is float32, every matmul at ``Precision.HIGHEST``.
It imports nothing of the program: each layer's weights are drawn again from
the seed by the benchmark's own ``bench.lib.weights``, one layer at a time,
so that the whole model never has to fit in float32.

``control=True`` computes every matmul on float8 (e4m3) operands, each row
of the activations and each column of the weights scaled to the format's
range, accumulating in float32: the precision one step below the bfloat16
the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512            # query rows per attention block
HEAD_ROWS = 512          # positions per vocabulary-projection call
BUDGET = 3 << 30         # float32 temporaries a layer call may hold


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control):
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd) at positions 0..S-1; rotates the two halves."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention; q: (B, S, H, hd), k and v: (B, S, K, hd)."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    qb = Q_BLOCK if s % Q_BLOCK == 0 else s
    pos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        logits = jnp.einsum("bqhd,bshd->bhqs", qi, k,
                            precision=HIGHEST) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        logits = jnp.where(pos[None, :] <= qpos[:, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // qb))          # (n, B, qb, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


@functools.partial(jax.jit, static_argnames=("w", "control"))
def _layer(x, key, index, w, control):
    lw = {n: a.astype(jnp.float32)
          for n, a in weights.layer(key, index, w).items()}
    b, s, _ = x.shape
    h = _rms(x, lw["ln1"], w.norm_eps)
    q = _mm(h, lw["wq"], control).reshape(b, s, w.heads, w.head_dim)
    k = _mm(h, lw["wk"], control).reshape(b, s, w.kv_heads, w.head_dim)
    v = _mm(h, lw["wv"], control).reshape(b, s, w.kv_heads, w.head_dim)
    a = _attention(_rope(q, w.rope_theta), _rope(k, w.rope_theta), v)
    x = x + _mm(a.reshape(b, s, -1), lw["wo"], control)
    h = _rms(x, lw["ln2"], w.norm_eps)
    m = jax.nn.silu(_mm(h, lw["wi"], control)) * _mm(h, lw["wu"], control)
    return x + _mm(m, lw["wd"], control)


@functools.partial(jax.jit, static_argnames=("w",))
def _embed(tokens, key, w):
    return weights.embedding(key, w)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("w", "control"))
def _head(hid, key, scored, w, control):
    """hid: (P, d) last-layer rows; scored: (K, P) token ids.  The best
    logit, the logits of the scored tokens, and the argmax, per row."""
    h = _rms(hid, weights.final_norm(key, w).astype(jnp.float32), w.norm_eps)
    logits = _mm(h, weights.unembedding(key, w).astype(jnp.float32), control)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits[None], scored[..., None], -1)[..., 0]
    return best, got, jnp.argmax(logits, -1).astype(jnp.int32)


def rows_for(w, length: int) -> int:
    """Sequences per layer call, so that a call's float32 temporaries (the
    MLP's three ``ff``-wide products and one block of attention scores)
    stay near ``BUDGET``."""
    per_row = 4 * length * (3 * w.ff + 6 * w.d) \
        + 4 * w.heads * (Q_BLOCK if length % Q_BLOCK == 0 else length) \
        * length
    return max(1, BUDGET // per_row)


def score(seed: int, w, seqs: list, starts: list, token_sets: list,
          pad_to: int, control: bool = False):
    """Run the reference over ``seqs`` (token lists, each at most ``pad_to``
    long, right-padded to it) and read the rows from ``starts[i]`` to the
    end of each.  ``token_sets[k][i]`` holds a token id for each read row of
    sequence ``i``.  Returns, over all read rows in order, ``best`` (the
    largest logit), ``scored`` (K, rows: the logit of each set's token) and
    ``argmax`` (the reference's own first token)."""
    key = weights.seed_key(seed)
    per = min(rows_for(w, pad_to), len(seqs))
    hid = []
    for lo in range(0, len(seqs), per):
        chunk = seqs[lo:lo + per]
        toks = np.zeros((per, pad_to), np.int32)
        for i, s in enumerate(chunk):
            toks[i, :len(s)] = s
        x = _embed(jnp.asarray(toks), key, w)
        for layer in range(w.layers):
            x = _layer(x, key, layer, w, control)
        x = np.asarray(x)
        for i, s in enumerate(chunk):
            hid.append(x[i, starts[lo + i]:len(s)])
    hid = np.concatenate(hid)
    sets = np.stack([np.concatenate([np.asarray(t[i], np.int32)
                                     for i in range(len(seqs))])
                     for t in token_sets])
    n = len(hid)
    pad = -n % HEAD_ROWS
    hid = np.pad(hid, ((0, pad), (0, 0)))
    sets = np.pad(sets, ((0, 0), (0, pad)))
    best, got, arg = [], [], []
    for lo in range(0, n + pad, HEAD_ROWS):
        b, g, a = _head(jnp.asarray(hid[lo:lo + HEAD_ROWS]), key,
                        jnp.asarray(sets[:, lo:lo + HEAD_ROWS]), w, control)
        best.append(np.asarray(b))
        got.append(np.asarray(g))
        arg.append(np.asarray(a))
    return (np.concatenate(best)[:n], np.concatenate(got, 1)[:, :n],
            np.concatenate(arg)[:n])
