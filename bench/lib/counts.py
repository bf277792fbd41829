"""Operations and bytes that the algorithm needs, from shapes alone.

These are the numerators of every roofline and MFU share.  They count what a
decoder must do, never what one implementation happens to do: no allocated
cache length, no padding, no copies.  So a later change to the program moves
the measured time and never these counts.

- Decode step, per active sequence: every weight once for the whole batch
  (except the embedding table, of which only the looked-up rows count); the
  K/V of the positions that sequence attends to, read, and its new row,
  written.  FLOPs: 2 x matmul parameters per token, plus attention over the
  attended positions.
- Prefill of one prompt: 2 x matmul parameters per real prompt token, the
  causal attention over the real tokens, and one row of the vocabulary
  projection (the next token's logits).
"""
from __future__ import annotations

BYTES = 2   # bfloat16 weights and cache


def layer_matmul_params(w) -> int:
    q, kv = w.heads * w.head_dim, w.kv_heads * w.head_dim
    return w.d * q + 2 * w.d * kv + q * w.d + 3 * w.d * w.ff


def matmul_params(w) -> int:
    """Every matmul weight a token passes through: the layers and the
    vocabulary projection (the embedding lookup is no matmul)."""
    return w.layers * layer_matmul_params(w) + w.d * w.vocab


def streamed_weight_bytes(w) -> int:
    """Bytes of every weight but the embedding table."""
    norms = (2 * w.layers + 1) * w.d
    return (matmul_params(w) + norms) * BYTES


def kv_row_bytes(w) -> int:
    """K and V of one position, over all layers."""
    return 2 * w.layers * w.kv_heads * w.head_dim * BYTES


def attention_flops(w, attended: int) -> int:
    """One query token against ``attended`` positions, all layers."""
    return 4 * w.layers * w.heads * w.head_dim * attended


def decode_step(w, n_active: int, attended: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step of ``n_active`` sequences that
    attend to ``attended`` positions in all: each to the positions before
    the token it feeds, and to that token's own."""
    if n_active == 0:
        return 0, 0
    flops = 2 * matmul_params(w) * n_active + attention_flops(w, attended)
    nbytes = (streamed_weight_bytes(w) + n_active * w.d * BYTES
              + kv_row_bytes(w) * attended)
    return flops, nbytes


def prefill(w, prompt_len: int) -> int:
    """FLOPs to prefill one prompt of ``prompt_len`` real tokens."""
    s = int(prompt_len)
    layers = 2 * w.layers * layer_matmul_params(w) * s
    causal = attention_flops(w, s * (s + 1) // 2)
    return layers + causal + 2 * w.d * w.vocab
