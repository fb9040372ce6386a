"""Operations a dense GQA decoder's forward needs per output token.

Counted from the configuration's published widths (HF keys): every
projection matmul, the unembedding, and attention over the token's context
(QK^T and PV, 2 operations per multiply-add). Norms, RoPE, softmax and the
draft model's forward are not counted.
"""
from __future__ import annotations


def _widths(cfg: dict):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    return d, hq, hkv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def matmul_flops(cfg: dict) -> int:
    """Projection and unembedding operations for one token."""
    d, hq, hkv, hd, ff, layers = _widths(cfg)
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    return 2 * (layers * per_layer + d * cfg["vocab_size"])


def attention_flops_per_key(cfg: dict) -> int:
    """Attention operations for one query against one cached key."""
    d, hq, hkv, hd, ff, layers = _widths(cfg)
    return 4 * layers * hq * hd


def output_flops(cfg: dict, first: int, end: int) -> int:
    """Operations that produced the tokens at sequence positions ``first``
    .. ``end - 1``: the token at position p comes from the forward at
    position p - 1, which attends p keys."""
    n = max(0, end - first)
    keys = (first + end - 1) * n // 2          # sum of p over the range
    return n * matmul_flops(cfg) + keys * attention_flops_per_key(cfg)
