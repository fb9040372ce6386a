"""Operations and bytes of a DeepSeek-V2 block (latent attention, experts)
as the program computes it, for the metrics of such a configuration.

``flops.py`` counts a dense GQA block; a latent-attention + expert block
does other work per token, so its counts live here, from the
configuration's published widths (HF keys). The harness's ``output_flops``
is ``flops.py``'s count, N x M + K x A for N output tokens whose forwards
attended K context keys in all; ``context_keys`` recovers K exactly from it.

Operations per output token of the target forward (2 per multiply-add;
norms, rope, softmax, routing's top-k and the draft not counted):
  attention projections  W_q, W_dkv, the absorptions q_nope W_uk and
                         o_lat W_uv, and W_o, in every layer;
  attention per key      QK over the latent row (rank + rope) and PV over
                         its latent part, for every head and layer;
  the dense layers       a SwiGLU MLP of ``intermediate_size``;
  each MoE layer         the router over every published expert, the
                         shared experts, and the experts held here at their
                         expectation of top_k x held / published
                         evaluations per token;
  the unembedding.

Bytes of the latent kernel (``paged_latent_attention``): each call reads
every live row's context once, 576 x 2 B a position and layer (the pool
row as published, not its lanes of padding), and reads its queries and
writes its outputs.
"""
from __future__ import annotations

from . import flops

BYTES = 2              # bfloat16 pool, queries and outputs


def is_latent_moe(cfg: dict) -> bool:
    return "kv_lora_rank" in cfg and "n_routed_experts" in cfg


def _w(cfg: dict):
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        dense=cfg["first_k_dense_replace"],
        moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        routed=cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        held=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"], v=cfg["vocab_size"])


def matmul_flops(cfg: dict) -> float:
    """Projection, expert and unembedding operations for one token."""
    w = _w(cfg)
    d, h = w["d"], w["h"]
    attn = (d * h * (w["dn"] + w["dr"]) + d * (w["r"] + w["dr"])
            + h * w["dn"] * w["r"] + h * w["r"] * w["dv"] + h * w["dv"] * d)
    expert = 3 * d * w["fe"]
    per_moe = (d * w["routed"] + w["shared"] * expert
               + w["k"] * w["held"] / w["routed"] * expert)
    layers = cfg["num_hidden_layers"]
    return 2 * (layers * attn + w["dense"] * 3 * d * w["ff"]
                + w["moe"] * per_moe + d * w["v"])


def attention_flops_per_key(cfg: dict) -> int:
    """QK over the latent row and PV over its latent part, per key."""
    w = _w(cfg)
    return (2 * cfg["num_hidden_layers"] * w["h"]
            * (w["r"] + w["dr"] + w["r"]))


def context_keys(cfg: dict, output_tokens: int, output_flops: int) -> int:
    """The context keys the window's output tokens attended, summed, from
    the harness's ``output_flops`` (``flops.output_flops``)."""
    return ((output_flops - output_tokens * flops.matmul_flops(cfg))
            // flops.attention_flops_per_key(cfg))


def output_flops(cfg: dict, output_tokens: int, keys: int) -> float:
    return (output_tokens * matmul_flops(cfg)
            + keys * attention_flops_per_key(cfg))


def kernel_bytes(cfg: dict, keys: int, mean_accepted: float, steps: int,
                 rows: int, k: int) -> float:
    """HBM bytes of the latent kernel's calls in a window, both models
    (target and its draft), every layer: the context read once a call per
    row, about ``keys / mean_accepted`` positions summed over the row-steps
    (a verify commits ``mean_accepted`` tokens on one read), plus the
    queries read and the outputs written for ``rows`` rows a step, the
    draft window 2k and the verify window k + 1 tokens wide."""
    w = _w(cfg)
    layers = cfg["num_hidden_layers"]
    row = (w["r"] + w["dr"]) * BYTES
    context = 2 * layers * row * keys / mean_accepted
    window = steps * rows * layers * (2 * k + k + 1) * w["h"] * (
        w["r"] + w["dr"] + w["r"]) * BYTES
    return context + window
