"""Plain reference of the Qwen2 architecture, and the benchmark's weights.

The Qwen2 decoder as published (Qwen2 technical report, arXiv:2407.10671;
Qwen2.5, arXiv:2412.15115; the ``qwen2`` model of HF transformers): token
embedding; per layer RMSNorm, attention with biased q/k/v projections,
rotary embeddings on half-split heads (``rotate_half``), grouped-query heads,
causal softmax and an unbiased output projection, then RMSNorm and a SwiGLU
MLP (down(silu(gate(x)) * up(x))); final RMSNorm and the unembedding (the
embedding's transpose when ``tie_word_embeddings``).

Everything is float32 (weights upcast layer by layer) with matmuls at
``Precision.HIGHEST``, one sequence at a time, attention in blocks of
queries so that long contexts fit. ``control_gaps`` is the control of the
comparison: the same reference with every weight matrix rounded to float8
(e4m3, one scale per output), the next precision below the served
bfloat16, put in the program's place. Nothing
here imports the serving program. Two things are specific to the program
under test, and both are data, not code of the program: ``model_config``
names the program's configuration fields, and ``init_params`` lays the
random weights out the way the program takes them (its parameter tree, with
the layers stacked on a leading axis, and the vocabulary padded to a
multiple of 256 with room for the draft's mask token).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512       # queries per attention block
LOGIT_BLOCK = 512       # positions per unembedding block
FP8_MAX = 448.0         # largest finite float8_e4m3fn


def _widths(cfg):
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, hq, hkv, cfg.get("head_dim") or d // hq


def padded_vocab(cfg) -> int:
    return -(-(cfg["vocab_size"] + 1) // 256) * 256


def model_config(cfg) -> dict:
    """The serving program's ``ModelConfig`` fields for this configuration."""
    d, hq, hkv, hd = _widths(cfg)
    return dict(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, n_heads=hq,
        n_kv_heads=hkv, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=hd,
        rope_theta=float(cfg["rope_theta"]), qkv_bias=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"], source=cfg["source"])


def init_params(cfg, key):
    """Random weights in the program's parameter layout, in the
    configuration's ``torch_dtype``. Biases and norm scales are random too,
    so that the comparison sees them."""
    d, hq, hkv, hd = _widths(cfg)
    ff, n = cfg["intermediate_size"], cfg["num_hidden_layers"]
    vp = padded_vocab(cfg)
    dtype = jnp.dtype(cfg.get("torch_dtype", "float32"))
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, F32) * scale).astype(
            dtype)

    def norm_scale(shape):
        return (1.0 + normal(shape, 0.1)).astype(dtype)

    embed = {"embedding": normal((vp, d), 1 / math.sqrt(d))}
    if not cfg["tie_word_embeddings"]:
        embed["unembed"] = normal((vp, d), 1 / math.sqrt(d))
    layers = {
        "norm1": {"scale": norm_scale((n, d))},
        "mixer": {
            "wq": normal((n, d, hq, hd), 1 / math.sqrt(d)),
            "wk": normal((n, d, hkv, hd), 1 / math.sqrt(d)),
            "wv": normal((n, d, hkv, hd), 1 / math.sqrt(d)),
            "wo": normal((n, hq, hd, d), 1 / math.sqrt(hq * hd)),
            "bq": normal((n, hq, hd), 0.1),
            "bk": normal((n, hkv, hd), 0.1),
            "bv": normal((n, hkv, hd), 0.1),
        },
        "norm2": {"scale": norm_scale((n, d))},
        "mlp": {
            "wi": normal((n, d, ff), 1 / math.sqrt(d)),
            "wg": normal((n, d, ff), 1 / math.sqrt(d)),
            "wo": normal((n, ff, d), 1 / math.sqrt(ff)),
        },
    }
    return {"embed": embed, "final_norm": {"scale": norm_scale((d,))},
            "prefix": [], "scan": [layers]}


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding of x [T, H, D] at positions 0 .. T-1."""
    t, _, dim = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _attention(q, k, v):
    """Causal grouped-query attention; q [T, Hq, D], k/v [T, Hkv, D]."""
    t, hq, dim = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    blocks = q.reshape(t // QUERY_BLOCK, QUERY_BLOCK, hkv, g, dim)
    key_pos = jnp.arange(t)

    def block(args):
        i, qb = args
        s = _mm("qhgd,khd->hgqk", qb, k) / math.sqrt(dim)
        q_pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        return _mm("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(t // QUERY_BLOCK), blocks))
    return out.reshape(t, hq, dim)


def to_fp8(w, axes):
    """``w`` rounded to float8 (e4m3) and back, with one scale per output:
    the largest magnitude over the contracted ``axes`` maps to 448."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def hidden_states(params, cfg, tokens, fp8=False):
    """Final-norm hidden states [T, d] of one sequence ``tokens`` [T]
    (``fp8``: with every weight matrix rounded by ``to_fp8``)."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = params["embed"]["embedding"][tokens].astype(F32)
    if fp8:
        x = to_fp8(x, (1,))

    def layer(x, p):
        p = jax.tree.map(lambda w: w.astype(F32), p)
        a, m = p["mixer"], p["mlp"]
        if fp8:
            a = dict(a, wq=to_fp8(a["wq"], (0,)), wk=to_fp8(a["wk"], (0,)),
                     wv=to_fp8(a["wv"], (0,)), wo=to_fp8(a["wo"], (0, 1)))
            m = {k: to_fp8(w, (0,)) for k, w in m.items()}
        h = _rms_norm(x, p["norm1"]["scale"], eps)
        q = _rope(_mm("td,dhk->thk", h, a["wq"]) + a["bq"], theta)
        k = _rope(_mm("td,dhk->thk", h, a["wk"]) + a["bk"], theta)
        v = _mm("td,dhk->thk", h, a["wv"]) + a["bv"]
        x = x + _mm("thk,hkd->td", _attention(q, k, v), a["wo"])
        h = _rms_norm(x, p["norm2"]["scale"], eps)
        up = jax.nn.silu(_mm("td,df->tf", h, m["wg"])) * _mm(
            "td,df->tf", h, m["wi"])
        return x + _mm("tf,fd->td", up, m["wo"]), None

    x, _ = jax.lax.scan(layer, x, params["scan"][0])
    return _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)


def next_token_gaps(params, cfg, tokens):
    """For each position i of ``tokens`` [T], how far the logit of
    tokens[i + 1] lies below the best logit there (the last position
    compares against tokens[0] and is meaningless). T is a multiple of 512.
    """
    v = cfg["vocab_size"]
    table = params["embed"].get("unembed", params["embed"]["embedding"])
    table = table[:v].astype(F32)
    x = hidden_states(params, cfg, tokens)
    nxt = jnp.roll(tokens, -1)
    t = tokens.shape[0]

    def block(args):
        xb, nb = args
        logits = _mm("td,vd->tv", xb, table)
        got = jnp.take_along_axis(logits, jnp.minimum(nb, v - 1)[:, None], 1)
        return jnp.max(logits, -1) - got[:, 0]

    gaps = jax.lax.map(block, (x.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1),
                               nxt.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK)))
    return gaps.reshape(t)


def control_gaps(params, cfg, tokens):
    """The control, at each position i of ``tokens`` [T]: how far the
    float32 logit of the token that the float8-weight reference puts first
    lies below the float32 best. Read at the positions of served tokens;
    T is a multiple of 512."""
    v = cfg["vocab_size"]
    table = params["embed"].get("unembed", params["embed"]["embedding"])
    table = table[:v].astype(F32)
    low_table = to_fp8(table, (1,))
    x = hidden_states(params, cfg, tokens)
    low = hidden_states(params, cfg, tokens, fp8=True)
    t = tokens.shape[0]

    def block(args):
        xb, lb = args
        logits = _mm("td,vd->tv", xb, table)
        pick = jnp.argmax(_mm("td,vd->tv", lb, low_table), -1)
        got = jnp.take_along_axis(logits, pick[:, None], 1)
        return jnp.max(logits, -1) - got[:, 0]

    gaps = jax.lax.map(block, (x.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1),
                               low.reshape(t // LOGIT_BLOCK, LOGIT_BLOCK, -1)))
    return gaps.reshape(t)
