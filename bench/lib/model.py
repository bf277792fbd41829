"""A configuration file's sizes, as the benchmark and the program read them.

Configuration files use the key names of the model's published
``config.json``; ``Widths`` holds the sizes the benchmark's own code needs
(weights, reference, operation counts), and ``program_config`` translates
them into the program's ``ModelConfig``.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def widths(cfg: dict) -> Widths:
    heads = cfg["num_attention_heads"]
    return Widths(
        layers=cfg["num_hidden_layers"], d=cfg["hidden_size"], heads=heads,
        kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        norm_eps=float(cfg["rms_norm_eps"]))


def program_config(name: str, cfg: dict):
    """The program's ``ModelConfig`` for a Llama-style dense decoder.  The
    program's RMSNorm takes no epsilon from its configuration; a file that
    states another than the one it applies is refused, not run."""
    import inspect

    from repro.models.common import ModelConfig, apply_norm
    w = widths(cfg)
    eps = inspect.signature(apply_norm).parameters["eps"].default
    if w.norm_eps != eps:
        raise ValueError(f"{name}: rms_norm_eps {w.norm_eps} states what the "
                         f"program cannot honour; its RMSNorm applies {eps}")
    dtype = cfg.get("torch_dtype", "bfloat16")
    return ModelConfig(
        name=name, family="dense", num_layers=w.layers, d_model=w.d,
        num_heads=w.heads, num_kv_heads=w.kv_heads, d_ff=w.ff,
        vocab_size=w.vocab, head_dim=w.head_dim, rope_theta=w.rope_theta,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        param_dtype=dtype, compute_dtype=dtype)
