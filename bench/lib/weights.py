"""Seeded weights, made on the device in the program's parameter layout.

The weights are the benchmark's input, like its prompts.  ``build`` makes all
of them in one jitted call whose only argument is the key, so one compiled
program serves every seed, and the reference (``bench/references``) makes any
single layer again from the seed alone, bit for bit.

Every matrix is a uniform draw with the variance of ``1 / fan_in``; norm
scales are uniform on [0.75, 1.25), so a norm applied in the wrong place or
left out shows in the logits.  Each value is a counter-based hash of its
position in its leaf, salted by the key, the layer and the leaf: two rounds
of the murmur3 finaliser, some twenty integer operations an element where
the threefry generator takes a few hundred, so drawing eight gigabytes is
bound by writing them.  Every element is computed from its own index, so
drawing the stacked layers under ``vmap`` gives the same bits as drawing one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# salt tags of the leaves outside the layer stack
_EMBED, _UNEMBED, _FINAL_NORM = 1 << 20, (1 << 20) + 1, (1 << 20) + 2
# per-layer leaves, in the order of their salt tags
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi", "wu", "wd")
_GOLDEN = 0x9E3779B9
_MASK32 = 0xFFFFFFFF


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _fmix(x):
    """The murmur3 32-bit finaliser: a bijection whose every output bit
    depends on every input bit."""
    x = x ^ (x >> 16)
    x = x * _u32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _u32(0xC2B2AE35)
    return x ^ (x >> 16)


def seed_key(seed: int):
    """The key of any whole-number seed: its low and high 32 bits."""
    seed = int(seed)
    return jnp.asarray(np.array([seed & _MASK32, (seed >> 32) & _MASK32],
                                np.uint32))


def _salt(key, index, tag: int):
    """One leaf's salt: the key, the layer ``index`` (traced or not) and
    the leaf's ``tag``."""
    s = _fmix(key[0] ^ _fmix(key[1] + _u32(_GOLDEN)))
    return _fmix(s ^ (_u32(index) * _u32(_GOLDEN) + _u32(tag)))


def _bits24(shape, salt):
    """24 hashed bits per element of ``shape``, as int32 in [0, 2**24)."""
    i = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    for ax in range(1, len(shape)):
        i = i * _u32(shape[ax]) + jax.lax.broadcasted_iota(jnp.uint32, shape,
                                                          ax)
    return (_fmix(_fmix(i ^ salt) + salt) >> 8).astype(jnp.int32)


def _uniform(salt, shape, std, dtype):
    """Uniform on [-half, half) with ``half = std * sqrt(3)``.  One
    rounding (a product with a constant), so the bits do not depend on
    how the compiler fuses the draw."""
    half = std * math.sqrt(3.0)
    centred = (_bits24(shape, salt) - (1 << 23)).astype(jnp.float32)
    return (centred * jnp.float32(half / (1 << 23))).astype(dtype)


def _scale(salt, d, dtype):
    """Uniform on [0.75, 1.25): a power-of-two product, exact, then one
    rounding."""
    u = _bits24((d,), salt).astype(jnp.float32) * jnp.float32(2.0 ** -25)
    return (u + jnp.float32(0.75)).astype(dtype)


def layer_shapes(w) -> dict:
    q, kv = w.heads * w.head_dim, w.kv_heads * w.head_dim
    return {"ln1": (w.d,), "ln2": (w.d,), "wq": (w.d, q), "wk": (w.d, kv),
            "wv": (w.d, kv), "wo": (q, w.d), "wi": (w.d, w.ff),
            "wu": (w.d, w.ff), "wd": (w.ff, w.d)}


def layer(key, index, w, dtype=jnp.bfloat16) -> dict:
    """Layer ``index``'s leaves by name."""
    out = {}
    for tag, (name, shape) in enumerate(layer_shapes(w).items()):
        salt = _salt(key, index, tag)
        if name.startswith("ln"):
            out[name] = _scale(salt, shape[0], dtype)
        else:
            out[name] = _uniform(salt, shape, shape[0] ** -0.5, dtype)
    return out


def embedding(key, w, dtype=jnp.bfloat16):
    return _uniform(_salt(key, 0, _EMBED), (w.vocab, w.d), 1.0, dtype)


def unembedding(key, w, dtype=jnp.bfloat16):
    return _uniform(_salt(key, 0, _UNEMBED), (w.d, w.vocab), w.d ** -0.5,
                    dtype)


def final_norm(key, w, dtype=jnp.bfloat16):
    return _scale(_salt(key, 0, _FINAL_NORM), w.d, dtype)


def program_params(key, w, dtype=jnp.bfloat16) -> dict:
    """Every weight, in the layout ``repro.models.transformer`` reads:
    layers stacked on a leading axis."""
    ls = jax.vmap(lambda i: layer(key, i, w, dtype))(
        jnp.arange(w.layers, dtype=jnp.uint32))
    return {
        "embed": {"embedding": embedding(key, w, dtype),
                  "unembed": {"w": unembedding(key, w, dtype)}},
        "layers": {
            "ln1": {"scale": ls["ln1"]}, "ln2": {"scale": ls["ln2"]},
            "attn": {n: {"w": ls[n]} for n in ("wq", "wk", "wv", "wo")},
            "mlp": {n: {"w": ls[n]} for n in ("wi", "wu", "wd")},
        },
        "final_norm": {"scale": final_norm(key, w, dtype)},
    }


def build(seed: int, w):
    """All weights on the default device in one jitted call.  The key is
    an argument, so every seed runs one program."""
    fn = jax.jit(functools.partial(program_params, w=w))
    return jax.block_until_ready(fn(seed_key(seed)))
