"""Shared building blocks: norms, RoPE, embeddings, dense MLP, MoE.

Everything is functional: ``init_*`` returns a param pytree (plain dicts of
jnp arrays), ``*_apply`` consumes it. Param leaf names are load-bearing —
the sharding rules in ``repro.sharding.specs`` map leaf names to logical
mesh axes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import scopes

Array = jax.Array

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(params, x: Array, eps: float = 1e-5, gemma_style: bool = False) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    scale = params["scale"]
    if gemma_style:  # gemma multiplies by (1 + scale)
        y = y * (1.0 + scale)
    else:
        y = y * scale
    return y.astype(dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm_apply(params, x: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(dtype)


def make_norm(cfg):
    """Returns (init_fn, apply_fn) per the config's norm flavour."""
    if cfg.use_layernorm:
        return init_layernorm, lambda p, x: layernorm_apply(p, x, cfg.norm_eps)
    gemma = cfg.post_block_norms  # gemma2 uses (1+scale) RMSNorm
    return init_rmsnorm, lambda p, x: rmsnorm_apply(p, x, cfg.norm_eps, gemma_style=gemma)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


# DeepSeek-V2's published YaRN constants
YARN_ORIGINAL_MAX_POS = 4096
YARN_BETA_FAST = 32
YARN_BETA_SLOW = 1


def yarn_correction_range(cfg, dim: int):
    """The rope channel pairs [low, high] over which YaRN ramps from the
    original frequencies to the interpolated ones (DeepSeek-V2
    ``yarn_find_correction_range``): below ``low`` a pair turns more than
    ``YARN_BETA_FAST`` times over the original context and keeps its
    frequency, above ``high`` less than ``YARN_BETA_SLOW`` times and is
    divided by the factor."""
    def pair(rotations):
        return dim * math.log(YARN_ORIGINAL_MAX_POS
                              / (rotations * 2 * math.pi)) / (
            2 * math.log(cfg.rope_theta))
    low = math.floor(pair(YARN_BETA_FAST))
    high = math.ceil(pair(YARN_BETA_SLOW))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(head_dim: int, theta: float, yarn_cfg=None) -> Array:
    """Inverse frequencies of the ``head_dim // 2`` rope pairs; with a
    ``yarn_cfg`` (a ModelConfig with ``yarn_factor`` set) YaRN's blend of
    the original and the factor-divided frequencies."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn_cfg is None or not yarn_cfg.yarn_factor:
        return freqs
    low, high = yarn_correction_range(yarn_cfg, head_dim)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / yarn_cfg.yarn_factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: Array, positions: Array, theta: float,
               yarn_cfg=None) -> Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (int).
    ``yarn_cfg``: YaRN frequencies. DeepSeek-V2 also scales cos and sin by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), which is 1:
    its published config gives the two mscales the same value."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, yarn_cfg)          # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    angles = angles[..., None, :]                          # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Softcap
# ---------------------------------------------------------------------------

def softcap(x: Array, cap: float) -> Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg):
    v, d = cfg.padded_vocab, cfg.d_model
    scale = 1.0 / math.sqrt(d)
    p = {"embedding": jax.random.normal(key, (v, d), jnp.float32) * scale}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["unembed"] = jax.random.normal(k2, (v, d), jnp.float32) * scale
    return p


def embed_apply(params, tokens: Array, cfg, dtype=jnp.bfloat16) -> Array:
    x = jnp.take(params["embedding"].astype(dtype), tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return x


def unembed_apply(params, x: Array, cfg) -> Array:
    table = params.get("unembed", params["embedding"]).astype(x.dtype)
    logits = jnp.einsum("...d,vd->...v", x, table)
    logits = softcap(logits, cfg.final_softcap)
    # mask padded vocab rows so they can never be sampled
    if cfg.padded_vocab != cfg.vocab_size:
        neg = jnp.asarray(-1e9, logits.dtype)
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad, neg, logits)
    return logits


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU; whisper uses GELU — flag via act)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, gated: bool = True):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {"wi": jax.random.normal(k1, (d_model, d_ff), jnp.float32) * s_in,
         "wo": jax.random.normal(k3, (d_ff, d_model), jnp.float32) * s_out}
    if gated:
        p["wg"] = jax.random.normal(k2, (d_model, d_ff), jnp.float32) * s_in
    return p


def mlp_apply(params, x: Array, act: str = "silu") -> Array:
    h = jnp.einsum("...d,df->...f", x, params["wi"].astype(x.dtype))
    if "wg" in params:
        g = jnp.einsum("...d,df->...f", x, params["wg"].astype(x.dtype))
        if act == "gelu":
            h = jax.nn.gelu(g) * h
        else:
            h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h) if act == "gelu" else jax.nn.silu(h)
    # sharded serving seam (DESIGN.md §11/§13): exact ruleset all-gathers
    # the d_ff-sharded hidden BEFORE the down-projection (bitwise identity);
    # throughput ruleset contracts it row-parallel at canonical chunk
    # granularity, and the post-contraction gather becomes the MLP's single
    # psum; plain einsum without an activation mesh
    from ..kernels import ops
    y = ops.rowparallel_einsum("...f,fd->...d", h,
                               params["wo"].astype(x.dtype),
                               x_axis=-1, w_axis=0)
    return ops.gather_activation(y)


# ---------------------------------------------------------------------------
# MoE: a router over every expert, and the experts this device holds
# ---------------------------------------------------------------------------

def init_moe(key, cfg):
    """Router over all ``moe_num_experts``; the expert weights of the
    ``resolved_experts_held`` experts this device holds (expert
    parallelism: the others live on other devices)."""
    e, held = cfg.moe_num_experts, cfg.resolved_experts_held
    d, f = cfg.d_model, (cfg.moe_d_ff or cfg.d_ff)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) * s_in,
        "we_i": jax.random.normal(k2, (held, d, f), jnp.float32) * s_in,
        "we_g": jax.random.normal(k3, (held, d, f), jnp.float32) * s_in,
        "we_o": jax.random.normal(k4, (held, f, d), jnp.float32) * s_out,
    }
    if cfg.moe_num_shared:
        p["shared"] = init_mlp(k5, d, f * cfg.moe_num_shared)
    return p


def moe_route(params, xt: Array, cfg):
    """Softmax over every expert's logit, then the top-k. The gates are
    renormalised over the k (``moe_norm_topk``) or, as DeepSeek-V2-Lite
    publishes, left as they are (its ``routed_scaling_factor`` is 1).
    xt: [n, d] -> (probs [n, e] f32, gates [n, k] f32, expert ids [n, k])."""
    logits = jnp.einsum("nd,de->ne", xt, params["router"].astype(xt.dtype)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # [n, e]
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.moe_top_k)  # [n, k]
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                                 + 1e-9)
    return probs, gate_vals, gate_idx


def moe_held_experts(params, xt: Array, gate_vals: Array, gate_idx: Array,
                     first_expert: int = 0):
    """The held experts' part of the layer, dropless: every (token, expert)
    pair routed to an expert held here is computed, and no other. The
    pairs are sorted by expert and each held expert's weights multiply its
    run of rows in one grouped matmul (``ops.grouped_matmul``); pairs
    routed to experts held elsewhere sort last, past every group, and are
    not computed. Held experts are ``first_expert`` .. ``first_expert + E``,
    E the leading axis of the expert weights.

    xt: [n, d]; gate_vals, gate_idx: [n, k].
    Returns (y [n, d], rows: int32 scalar count of the rows computed)."""
    n, d = xt.shape
    k = gate_idx.shape[1]
    held = params["we_i"].shape[0]
    local = gate_idx.reshape(-1) - first_expert                # [n*k]
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)                            # [E]
    rows = jnp.sum(sizes)
    xs = jnp.take(xt, order // k, axis=0)                       # [n*k, d]
    dt = xt.dtype
    from ..kernels import ops
    hi = ops.grouped_matmul(xs, params["we_i"].astype(dt), sizes)
    hg = ops.grouped_matmul(xs, params["we_g"].astype(dt), sizes)
    ye = ops.grouped_matmul(jax.nn.silu(hg) * hi, params["we_o"].astype(dt),
                            sizes)                               # [n*k, d]
    # rows past the groups hold no result: zero them, then return each
    # pair to its (token, slot) place and sum a token's slots
    ye = jnp.where((jnp.arange(n * k) < rows)[:, None], ye, 0)
    back = jnp.argsort(order)
    w = jnp.where(mine, gate_vals.reshape(-1), 0.0)             # [n*k]
    y = jnp.sum((jnp.take(ye, back, axis=0).astype(jnp.float32)
                 * w[:, None]).reshape(n, k, d), axis=1)
    return y.astype(dt), rows


def _moe_capacity(params, xt, gate_vals, gate_idx, cfg, group_size):
    """GShard-style grouped one-hot dispatch with per-group expert capacity
    ``cap = factor * g * k / e`` (training). Everything is einsum/one-hot —
    no sort, no scatter — which is what GSPMD partitions well (a
    distributed argsort at 1M tokens compiles pathologically, and the
    ungrouped one-hot dispatch tensor [n, e, n*k/e] is O(n^2)). Dispatch
    overhead is G*g*e*cap*d = n*e*cap*d, a few percent of the expert FLOPs
    at g=256. Tokens past an expert's capacity are dropped. Returns
    (y [n, d], rows computed, one-hot [n, k, e])."""
    n, d = xt.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    held = params["we_i"].shape[0]
    g = min(group_size, n)
    while n % g:
        g //= 2
    cap = min(max(4, int(cfg.moe_capacity_factor * g * k / e)), g)
    ng = n // g

    idx_g = gate_idx.reshape(ng, g, k)
    gate_g = gate_vals.reshape(ng, g, k).astype(xt.dtype)
    x_g = xt.reshape(ng, g, d)

    # rank of each (token, slot) within its expert, per group
    onehot = jax.nn.one_hot(idx_g, e, dtype=jnp.int32)       # [G, g, k, e]
    flat = onehot.reshape(ng, g * k, e)
    rank = (jnp.cumsum(flat, axis=1) - flat).reshape(ng, g, k, e)
    pos = jnp.sum(rank * onehot, axis=-1)                    # [G, g, k]
    keep = pos < cap
    gate_g = gate_g * keep.astype(xt.dtype)

    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap, dtype=xt.dtype)
    oh = onehot[..., :held].astype(xt.dtype)                 # held experts
    disp = jnp.einsum("Ggke,Ggkc->Ggec", oh, pos_oh)         # [G, g, E, cap]
    comb = jnp.einsum("Ggec,Ggk,Ggke->Ggec", disp, gate_g, oh)

    xe = jnp.einsum("Ggec,Ggd->Gecd", disp, x_g)             # [G, E, cap, d]
    hi = jnp.einsum("Gecd,edf->Gecf", xe, params["we_i"].astype(xt.dtype))
    hg = jnp.einsum("Gecd,edf->Gecf", xe, params["we_g"].astype(xt.dtype))
    he = jax.nn.silu(hg) * hi
    from ..kernels import ops
    # d_ff-sharded row-parallel down-projection seam (throughput ruleset)
    ye = ops.rowparallel_einsum("Gecf,efd->Gecd", he,
                                params["we_o"].astype(xt.dtype),
                                x_axis=-1, w_axis=1)
    y = jnp.einsum("Ggec,Gecd->Ggd", comb, ye).reshape(n, d)
    return y, jnp.int32(ng * held * cap), onehot.reshape(n, k, e)


def moe_apply(params, x: Array, cfg, return_aux: bool = False,
              dropless: bool = False, group_size: int = 256):
    """x: [B, T, D]. Top-k routing over every expert, then the part of the
    layer that the held experts give, plus the shared experts.

    ``dropless=True`` (every cached forward: serving prefill chunks,
    decode and verify) computes each routed pair on a held expert
    (``moe_held_experts``): routing must be independent of batch
    composition or lossless speculative decoding would diverge from AR.
    Otherwise (training) the capacity dispatch of ``_moe_capacity``.
    ``aux["moe_rows"]``: the expert rows computed."""
    b, t, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    xt = x.reshape(b * t, d)
    n = b * t
    with jax.named_scope(scopes.MOE_ROUTE):
        probs, gate_vals, gate_idx = moe_route(params, xt, cfg)
    with jax.named_scope(scopes.MOE_EXPERTS):
        if dropless:
            y, rows = moe_held_experts(params, xt, gate_vals, gate_idx)
            onehot = None
        else:
            y, rows, onehot = _moe_capacity(params, xt, gate_vals, gate_idx,
                                            cfg, group_size)
        if "shared" in params:
            y = y + mlp_apply(params["shared"], xt)
    from ..kernels import ops
    y = ops.gather_activation(y).reshape(b, t, d)

    if return_aux:
        # Switch-style load balance loss
        if onehot is None:
            onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)
        me = jnp.mean(probs, axis=0)                         # [e]
        ce = jnp.mean(jnp.sum(onehot, axis=1).astype(jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        return y, {"load_balance_loss": aux, "expert_fraction": ce,
                   "moe_rows": rows}
    return y
