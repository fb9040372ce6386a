"""Plain reference of the DeepSeek-V2 architecture, and the benchmark's weights.

The DeepSeek-V2 decoder as published (arXiv:2405.04434; the
``deepseek_v2`` model code that ships with DeepSeek-V2-Lite,
``modeling_deepseek.py``): token embedding; per layer RMSNorm, multi-head
latent attention, RMSNorm and a feed-forward part; final RMSNorm and an
untied unembedding.

Attention (MLA, no query LoRA): per head a query of ``qk_nope`` + ``qk_rope``
channels; the keys and values come from one compressed row per position,
``[c_kv | k_rope]`` = ``h W_dkv``, with RMSNorm on ``c_kv`` and the
per-head keys ``[c_kv W_uk | k_rope]`` and values ``c_kv W_uv`` expanded
here, as the published code expands them (no absorption); rotary
embeddings with YaRN scaling (``DeepseekV2YarnRotaryEmbedding``) on the
rope channels of the query and the shared key; causal softmax at
``softmax_scale`` = (qk_nope + qk_rope)^-1/2 x mscale(factor,
mscale_all_dim)^2.

Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU MLP of
``intermediate_size``; every other layer a mixture of experts: a softmax
over the router's logits for every published expert, the ``greedy`` top-k,
the k gates renormalised only when ``norm_topk_prob`` (else scaled by
``routed_scaling_factor``), each routed expert a SwiGLU MLP of
``moe_intermediate_size``, plus the shared experts as one SwiGLU MLP of
``n_shared_experts`` x ``moe_intermediate_size``.

Expert parallelism: ``n_routed_experts`` counts the experts held on this
chip (experts 0 .. n_routed_experts - 1); the router keeps its published
``n_routed_experts_published`` outputs. The part of each MoE layer that the
absent experts would add is left out here, as in the program: the layer is
the held experts' share plus the shared experts.

Everything is float32 (weights upcast layer by layer) with matmuls at
``Precision.HIGHEST``, one sequence at a time, attention in blocks of
queries and the experts in blocks of positions so that long contexts fit.
``control_gaps`` is the control of the comparison: the same reference with
every weight matrix rounded to float8 (e4m3, one scale per output), the
next precision below the served bfloat16, put in the program's place.

Departures from the published description, none of which changes the
mathematics the comparison checks:

- rope channel order: the published code de-interleaves the rope channels
  (pairs (2i, 2i+1)) before ``rotate_half``; here pair i is channels (i,
  i + d/2) throughout, the order the program uses. With random weights
  that is a permutation of the weights' rope columns.
- no cache and no kernels: each sequence runs whole.
- random weights from the seed; norm scales random too, so that the
  comparison sees them.

Nothing here imports the serving program. Two things are specific to the
program under test, and both are data, not code of the program:
``model_config`` names the program's configuration fields, and
``init_params`` lays the random weights out the way the program takes them
(its parameter tree: the leading dense layers as ``prefix``, the MoE layers
stacked on a leading axis as ``scan``, and the vocabulary padded to a
multiple of 256 with room for the draft's mask token).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512       # queries per attention block
EXPERT_BLOCK = 512      # positions per mixture-of-experts block
LOGIT_BLOCK = 512       # positions per unembedding block
FP8_MAX = 448.0         # largest finite float8_e4m3fn


def padded_vocab(cfg) -> int:
    return -(-(cfg["vocab_size"] + 1) // 256) * 256


def router_experts(cfg) -> int:
    """The router's outputs: every published expert."""
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def _yarn(cfg):
    rs = cfg.get("rope_scaling") or {}
    return rs if rs.get("type") == "yarn" else None


def yarn_mscale(scale, mscale=1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = _yarn(cfg)
    if rs and rs.get("mscale_all_dim"):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        scale *= m * m
    return scale


def model_config(cfg) -> dict:
    """The serving program's ``ModelConfig`` fields for this configuration."""
    assert cfg.get("q_lora_rank") is None, "query LoRA is not covered"
    assert cfg.get("moe_layer_freq", 1) == 1
    assert cfg.get("scoring_func", "softmax") == "softmax"
    assert cfg.get("topk_method", "greedy") == "greedy"
    # published values the program takes as constants
    assert cfg["routed_scaling_factor"] == 1 or cfg["norm_topk_prob"]
    rs = _yarn(cfg) or {}
    if rs:
        assert (rs["original_max_position_embeddings"], rs["beta_fast"],
                rs["beta_slow"]) == (4096, 32, 1)
        assert rs["mscale"] == rs["mscale_all_dim"]
    return dict(
        name=cfg["name"], arch_type="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_kind="mla", kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs.get("factor", 0.0)),
        yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        moe_num_experts=router_experts(cfg),
        moe_experts_held=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_num_shared=cfg["n_shared_experts"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_norm_topk=bool(cfg["norm_topk_prob"]),
        first_dense_layers=cfg["first_k_dense_replace"],
        first_dense_d_ff=cfg["intermediate_size"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"], source=cfg["source"])


def init_params(cfg, key):
    """Random weights in the program's parameter layout, in the
    configuration's ``torch_dtype``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], (cfg["n_shared_experts"]
                                         * cfg["moe_intermediate_size"])
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    vp = padded_vocab(cfg)
    dtype = jnp.dtype(cfg.get("torch_dtype", "float32"))
    keys = iter(jax.random.split(key, 64))

    def normal(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def norm_scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)
                ).astype(dtype)

    def mlp(lead, width):
        return {"wi": normal(lead + (d, width), d),
                "wg": normal(lead + (d, width), d),
                "wo": normal(lead + (width, d), width)}

    def layers(lead, moe):
        p = {
            "norm1": {"scale": norm_scale(lead + (d,))},
            "mixer": {
                "w_q": normal(lead + (d, h, dn + dr), d),
                "w_dkv": normal(lead + (d, r + dr), d),
                "kv_lora_norm": norm_scale(lead + (r,)),
                "w_uk": normal(lead + (r, h, dn), r),
                "w_uv": normal(lead + (r, h, dv), r),
                "wo": normal(lead + (h, dv, d), h * dv),
            },
            "norm2": {"scale": norm_scale(lead + (d,))},
        }
        if moe:
            p["mlp"] = {
                "router": normal(lead + (d, router_experts(cfg)), d),
                "we_i": normal(lead + (held, d, fe), d),
                "we_g": normal(lead + (held, d, fe), d),
                "we_o": normal(lead + (held, fe, d), fe),
                "shared": mlp(lead, fs),
            }
        else:
            p["mlp"] = mlp(lead, ff)
        return p

    embed = {"embedding": normal((vp, d), d)}
    if not cfg["tie_word_embeddings"]:
        embed["unembed"] = normal((vp, d), d)
    return {"embed": embed, "final_norm": {"scale": norm_scale((d,))},
            "prefix": [layers((), False) for _ in range(n_dense)],
            "scan": [layers((n_moe,), True)]}


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _inv_freq(cfg):
    """Rope inverse frequencies, YaRN-blended when the config scales."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    rs = _yarn(cfg)
    if not rs:
        return inv, 1.0

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    extra = 1.0 - ramp                   # share of the original frequency
    inv = inv / rs["factor"] * (1.0 - extra) + inv * extra
    m = (yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
         / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))
    return inv, m


def _rope(x, cfg):
    """Rotary embedding of x [T, H, D] at positions 0 .. T-1."""
    t, _, dim = x.shape
    inv, m = _inv_freq(cfg)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * (jnp.cos(ang) * m) + rotated * (jnp.sin(ang) * m)


def _attention(q, k, v, scale):
    """Causal multi-head attention; q/k [T, H, Dqk], v [T, H, Dv]."""
    t, h, dqk = q.shape
    blocks = q.reshape(t // QUERY_BLOCK, QUERY_BLOCK, h, dqk)
    key_pos = jnp.arange(t)

    def block(args):
        i, qb = args
        s = _mm("qhd,khd->hqk", qb, k) * scale
        q_pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(t // QUERY_BLOCK), blocks))
    return out.reshape(t, h, v.shape[-1])


def _mlp(h, m):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, m["wg"]))
               * _mm("td,df->tf", h, m["wi"]), m["wo"])


def _moe(h, m, cfg):
    """The held experts' share of the layer plus the shared experts."""
    held, k = m["we_i"].shape[0], cfg["num_experts_per_tok"]

    def block(hb):
        probs = jax.nn.softmax(_mm("td,de->te", hb, m["router"]), -1)
        gates, idx = jax.lax.top_k(probs, k)
        if cfg["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        else:
            gates = gates * cfg["routed_scaling_factor"]
        # each held expert's weight per position: its gate if routed there
        weight = jnp.sum(jnp.where(idx[:, :, None] == jnp.arange(held),
                                   gates[:, :, None], 0.0), axis=1)  # [t, E]
        up = jax.nn.silu(_mm("td,edf->tef", hb, m["we_g"])) * _mm(
            "td,edf->tef", hb, m["we_i"])
        out = _mm("tef,efd->ted", up, m["we_o"])
        return jnp.sum(out * weight[:, :, None], axis=1) + _mlp(hb,
                                                                m["shared"])

    t = h.shape[0]
    out = jax.lax.map(block, h.reshape(t // EXPERT_BLOCK, EXPERT_BLOCK, -1))
    return out.reshape(t, -1)


def to_fp8(w, axes):
    """``w`` rounded to float8 (e4m3) and back, with one scale per output:
    the largest magnitude over the contracted ``axes`` maps to 448."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _fp8_layer(p):
    a, m = p["mixer"], p["mlp"]
    a = dict(a, w_q=to_fp8(a["w_q"], (0,)), w_dkv=to_fp8(a["w_dkv"], (0,)),
             w_uk=to_fp8(a["w_uk"], (0,)), w_uv=to_fp8(a["w_uv"], (0,)),
             wo=to_fp8(a["wo"], (0, 1)))

    def dense(d):
        return {"wi": to_fp8(d["wi"], (0,)), "wg": to_fp8(d["wg"], (0,)),
                "wo": to_fp8(d["wo"], (0,))}

    if "router" in m:
        m = {"router": to_fp8(m["router"], (0,)),
             "we_i": to_fp8(m["we_i"], (1,)), "we_g": to_fp8(m["we_g"], (1,)),
             "we_o": to_fp8(m["we_o"], (1,)), "shared": dense(m["shared"])}
    else:
        m = dense(m)
    return dict(p, mixer=a, mlp=m)


def hidden_states(params, cfg, tokens, fp8=False):
    """Final-norm hidden states [T, d] of one sequence ``tokens`` [T]
    (``fp8``: with every weight matrix rounded by ``to_fp8``)."""
    eps = float(cfg["rms_norm_eps"])
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    scale = softmax_scale(cfg)
    x = params["embed"]["embedding"][tokens].astype(F32)
    if fp8:
        x = to_fp8(x, (1,))

    def layer(x, p, moe):
        p = jax.tree.map(lambda w: w.astype(F32), p)
        if fp8:
            p = _fp8_layer(p)
        a = p["mixer"]
        h = _rms_norm(x, p["norm1"]["scale"], eps)
        q = _mm("td,dhk->thk", h, a["w_q"])
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], -1)
        ckv = _mm("td,dr->tr", h, a["w_dkv"])
        c = _rms_norm(ckv[:, :r], a["kv_lora_norm"], eps)
        k_rope = _rope(ckv[:, None, r:], cfg)                  # [T, 1, dr]
        k_nope = _mm("tr,rhk->thk", c, a["w_uk"])
        v = _mm("tr,rhk->thk", c, a["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:2]
                                      + k_rope.shape[2:])], -1)
        x = x + _mm("thk,hkd->td", _attention(q, k, v, scale), a["wo"])
        h = _rms_norm(x, p["norm2"]["scale"], eps)
        return x + (_moe(h, p["mlp"], cfg) if moe else _mlp(h, p["mlp"]))

    for p in params["prefix"]:
        x = layer(x, p, False)
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p, True), None), x,
                        params["scan"][0])
    return _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)


def _table(params, cfg):
    table = params["embed"].get("unembed", params["embed"]["embedding"])
    return table[:cfg["vocab_size"]].astype(F32)


def logits(params, cfg, tokens):
    """Logits [T, vocab] of one sequence (small sizes: tests)."""
    return _mm("td,vd->tv", hidden_states(params, cfg, tokens),
               _table(params, cfg))


def next_token_gaps(params, cfg, tokens):
    """For each position i of ``tokens`` [T], how far the logit of
    tokens[i + 1] lies below the best logit there (the last position
    compares against tokens[0] and is meaningless). T is a multiple of 512.
    """
    v = cfg["vocab_size"]
    table = _table(params, cfg)
    x = hidden_states(params, cfg, tokens)
    nxt = jnp.roll(tokens, -1)
    t = tokens.shape[0]

    def block(args):
        xb, nb = args
        lg = _mm("td,vd->tv", xb, table)
        got = jnp.take_along_axis(lg, jnp.minimum(nb, v - 1)[:, None], 1)
        return jnp.max(lg, -1) - got[:, 0]

    gaps = jax.lax.map(block, (x.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1),
                               nxt.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK)))
    return gaps.reshape(t)


def control_gaps(params, cfg, tokens):
    """The control, at each position i of ``tokens`` [T]: how far the
    float32 logit of the token that the float8-weight reference puts first
    lies below the float32 best. Read at the positions of served tokens;
    T is a multiple of 512."""
    table = _table(params, cfg)
    low_table = to_fp8(table, (1,))
    x = hidden_states(params, cfg, tokens)
    low = hidden_states(params, cfg, tokens, fp8=True)
    t = tokens.shape[0]

    def block(args):
        xb, lb = args
        lg = _mm("td,vd->tv", xb, table)
        pick = jnp.argmax(_mm("td,vd->tv", lb, low_table), -1)
        got = jnp.take_along_axis(lg, pick[:, None], 1)
        return jnp.max(lg, -1) - got[:, 0]

    gaps = jax.lax.map(block, (x.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1),
                               low.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1)))
    return gaps.reshape(t)
