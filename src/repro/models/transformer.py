"""Transformer assembly: decoder-only LM + whisper-style encoder-decoder.

The layer stack is executed as ``lax.scan`` over repeating *periods* (see
config.scan_plan) so 95-layer models lower to a small HLO. Params, caches and
SSM states for scanned layers are stacked on a leading ``n_repeats`` axis;
prefix layers (e.g. deepseek-v2's leading dense layer) run unrolled.
Attention pools ride the scan's carry, written and read in place at the
layer index; SSM states are a scan input and output.

Public entry points:
  init_params(key, cfg)                      -> param pytree
  init_caches(cfg, batch, num_blocks, block_size, dtype)
                                             -> cache pytree (decode)
  KV_BLOCK, blocks_for(n_tokens, block_size) -> the page size and arithmetic
  encode(params, cfg, frontend_embed)        -> encoder output (enc-dec only)
  forward(params, cfg, tokens, ...)          -> (logits, new_caches, aux)
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import (ATTN_CROSS, ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, MLP_DENSE,
                     MLP_MOE, MLP_NONE, SSM, LayerSpec, ModelConfig, scan_plan)
from . import attention as attn
from . import layers as L
from . import ssm as ssm_mod

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 8)
    init_norm, _ = L.make_norm(cfg)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.d_model)}
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        p["mixer"] = attn.init_gqa(ks[0], cfg)
    elif spec.mixer == ATTN_MLA:
        p["mixer"] = attn.init_mla(ks[0], cfg)
    elif spec.mixer == ATTN_CROSS:
        p["mixer"] = attn.init_cross_attn(ks[0], cfg)
    elif spec.mixer == SSM:
        p["mixer"] = ssm_mod.init_mamba2(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)

    if spec.mlp != MLP_NONE and not cfg.parallel_block:
        p["norm2"] = init_norm(cfg.d_model)
    if spec.mlp == MLP_DENSE:
        d_ff = cfg.first_dense_d_ff or cfg.d_ff
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, d_ff, gated=cfg.mlp_gated)
    elif spec.mlp == MLP_MOE:
        p["mlp"] = L.init_moe(ks[1], cfg)

    if cfg.post_block_norms:
        p["post_norm1"] = init_norm(cfg.d_model)
        if spec.mlp != MLP_NONE:
            p["post_norm2"] = init_norm(cfg.d_model)

    if cfg.is_encoder_decoder:  # whisper decoder: self + cross per layer
        p["cross"] = attn.init_gqa(ks[2], cfg)
        p["norm_cross"] = init_norm(cfg.d_model)
    return p


def _init_encoder_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    init_norm, _ = L.make_norm(cfg)
    return {"norm1": init_norm(cfg.d_model),
            "mixer": attn.init_gqa(ks[0], cfg),
            "norm2": init_norm(cfg.d_model),
            "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated)}


def init_params(key, cfg: ModelConfig):
    plan = scan_plan(cfg)
    keys = jax.random.split(key, cfg.num_layers + 4)
    init_norm, _ = L.make_norm(cfg)

    params: Dict[str, Any] = {
        "embed": L.init_embedding(keys[-1], cfg),
        "final_norm": init_norm(cfg.d_model),
    }
    params["prefix"] = [
        _init_layer(keys[i], cfg, spec) for i, spec in enumerate(plan.prefix)]

    scanned = []
    base = len(plan.prefix)
    for j, spec in enumerate(plan.period):
        # one stacked tree per period position: leading dim n_repeats
        per_repeat = [
            _init_layer(keys[base + r * len(plan.period) + j], cfg, spec)
            for r in range(plan.n_repeats)]
        scanned.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))
    params["scan"] = scanned

    if cfg.is_encoder_decoder:
        ek = jax.random.split(keys[-2], cfg.encoder_layers + 1)
        params["encoder"] = {
            "layers": [_init_encoder_layer(ek[i], cfg)
                       for i in range(cfg.encoder_layers)],
            "final_norm": init_norm(cfg.d_model),
        }
    return params


KV_BLOCK = 64   # tokens per KV page: the served page size


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks covering ``n_tokens`` positions (ceiling division)."""
    return -(-int(n_tokens) // block_size)


def _init_layer_cache(cfg, spec: LayerSpec, batch, num_blocks, block_size,
                      dtype):
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        return attn.init_gqa_pool(cfg, num_blocks, block_size, dtype)
    if spec.mixer == ATTN_MLA:
        return attn.init_mla_pool(cfg, num_blocks, block_size, dtype)
    if spec.mixer == SSM:
        return ssm_mod.init_mamba2_state(cfg, batch, jnp.float32)
    if spec.mixer == ATTN_CROSS:
        return {}
    raise ValueError(spec.mixer)


def init_caches(cfg: ModelConfig, batch: int, num_blocks: int,
                block_size: int, dtype=jnp.bfloat16):
    """The cache pytree every cached forward runs on (DESIGN.md §5):
    attention leaves are block pools ``[NB, bs, ...]`` shared by the rows
    through their block tables (block 0 the garbage block); SSM states stay
    per row, ``[batch, ...]``. Scanned layers carry a leading repeats dim.
    ``dtype`` accepts a kv_dtype name ("bf16"/"fp32"/"int8"/"fp8") or a
    jnp dtype; quantized dtypes add sibling *_scale pool leaves."""
    dtype = attn.resolve_kv_dtype(dtype)
    plan = scan_plan(cfg)

    def layer(s):
        return _init_layer_cache(cfg, s, batch, num_blocks, block_size, dtype)

    return {
        "prefix": [layer(s) for s in plan.prefix],
        "scan": [jax.tree.map(
            lambda x: jnp.broadcast_to(x, (plan.n_repeats,) + x.shape).copy()
            if hasattr(x, "shape") else x, layer(s))
            for s in plan.period],
    }


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _apply_layer(lp, cfg: ModelConfig, spec: LayerSpec, x, positions, *,
                 cache=None, cache_pos=None, mask_info=None, enc_out=None,
                 collect_ssm=False, block_tables=None, kv_block_size=0,
                 tree_info=None, layer=None):
    _, norm = L.make_norm(cfg)
    aux = {}
    h = norm(lp["norm1"], x)

    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.sliding_window if spec.mixer == ATTN_LOCAL else 0
        y, new_cache = attn.gqa_apply(
            lp["mixer"], cfg, h, positions, layer_window=window, cache=cache,
            cache_pos=cache_pos, mask_info=mask_info, use_rope=cfg.use_rope,
            block_tables=block_tables, kv_block_size=kv_block_size,
            tree_info=tree_info, layer=layer)
    elif spec.mixer == ATTN_MLA:
        y, new_cache = attn.mla_apply(lp["mixer"], cfg, h, positions,
                                      cache=cache, cache_pos=cache_pos,
                                      mask_info=mask_info,
                                      block_tables=block_tables,
                                      kv_block_size=kv_block_size,
                                      tree_info=tree_info, layer=layer)
    elif spec.mixer == ATTN_CROSS:
        y = attn.cross_attn_apply(lp["mixer"], cfg, h, enc_out)
        new_cache = cache
    elif spec.mixer == SSM:
        y, new_cache = ssm_mod.mamba2_apply(lp["mixer"], cfg, h, state=cache,
                                            collect_states=collect_ssm)
    else:
        raise ValueError(spec.mixer)

    if cfg.post_block_norms:
        y = norm(lp["post_norm1"], y)

    if cfg.parallel_block and spec.mlp != MLP_NONE:
        m = L.mlp_apply(lp["mlp"], h, act=cfg.mlp_act)
        x = x + y + m
        return x, (new_cache if new_cache is not None else {}), aux

    x = x + y

    if cfg.is_encoder_decoder:
        hc = norm(lp["norm_cross"], x)
        yc = attn.cross_attn_apply(lp["cross"], cfg, hc, enc_out)
        x = x + yc

    if spec.mlp != MLP_NONE:
        h2 = norm(lp["norm2"], x)
        if spec.mlp == MLP_MOE:
            # dropless routing on every cached forward (prefill, decode,
            # verify): routing must not depend on batch composition or
            # speculative decoding loses losslessness. Training keeps
            # capacity routing.
            m, moe_aux = L.moe_apply(lp["mlp"], h2, cfg, return_aux=True,
                                     dropless=cache_pos is not None)
            aux["load_balance_loss"] = moe_aux["load_balance_loss"]
            aux["moe_rows"] = moe_aux["moe_rows"]
        else:
            m = L.mlp_apply(lp["mlp"], h2, act=cfg.mlp_act)
        if cfg.post_block_norms:
            m = norm(lp["post_norm2"], m)
        x = x + m
    return x, (new_cache if new_cache is not None else {}), aux


def encode(params, cfg: ModelConfig, frontend_embed: Array) -> Array:
    """Whisper-style encoder over precomputed frame embeddings [B, S, D]."""
    _, norm = L.make_norm(cfg)
    x = frontend_embed
    s = x.shape[1]
    x = x + L.sinusoidal_positions(s, cfg.d_model).astype(x.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], x.shape[:2])
    for lp in params["encoder"]["layers"]:
        h = norm(lp["norm1"], x)
        y, _ = attn.gqa_apply(lp["mixer"], cfg, h, pos, causal=False,
                              use_rope=False)
        x = x + y
        h2 = norm(lp["norm2"], x)
        x = x + L.mlp_apply(lp["mlp"], h2, act=cfg.mlp_act)
    return norm(params["encoder"]["final_norm"], x)


def forward(params, cfg: ModelConfig, tokens: Array, positions=None, *,
            mask_info=None, enc_out=None, caches=None, cache_pos=None,
            collect_ssm=False, remat: bool = False, dtype=jnp.bfloat16,
            last_only: bool = False, block_tables=None, kv_block_size=0,
            tree_info=None):
    """Run the decoder stack.

    tokens:       [B, T] int32
    positions:    [B, T] absolute positions (default arange)
    caches:       pytree from init_caches (None = no-cache training/prefill
                  path): attention leaves are [NB, block, ...] pools, SSM
                  states batch-indexed
    cache_pos:    [B] int32 — write offset into the caches
    block_tables: [B, MBS] int32 — per-row block tables into the pools
                  (required with ``caches``)
    kv_block_size: tokens per KV block (static; required with ``caches``)
    tree_info:    optional attention.TreeAttnInfo — the tokens are a packed
                  speculative candidate tree; pass explicit depth-based
                  ``positions`` alongside (DESIGN.md §6)

    Returns (logits [B, T, padded_vocab], new_caches, aux); ``aux``
    holds the MoE load-balance loss, the pre-final-norm ``hidden`` states
    and ``moe_rows``, the expert rows the MoE layers computed.
    """
    plan = scan_plan(cfg)
    b, t = tokens.shape
    assert caches is None or (block_tables is not None and kv_block_size), \
        "a cached forward needs block_tables and kv_block_size"
    if positions is None:
        base = cache_pos[:, None] if cache_pos is not None else 0
        positions = base + jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    x = L.embed_apply(params["embed"], tokens, cfg, dtype=dtype)
    if cfg.abs_pos:
        pe = L.sinusoidal_positions(cfg.max_seq_len, cfg.d_model).astype(x.dtype)
        x = x + jnp.take(pe, positions, axis=0)

    _, norm = L.make_norm(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    rows = jnp.zeros((), jnp.int32)          # expert rows computed
    new_caches = {"prefix": [], "scan": []}

    def run(lp, spec, x, cache, layer=None):
        return _apply_layer(lp, cfg, spec, x, positions, cache=cache,
                            cache_pos=cache_pos, mask_info=mask_info,
                            enc_out=enc_out, collect_ssm=collect_ssm,
                            block_tables=block_tables,
                            kv_block_size=kv_block_size,
                            tree_info=tree_info, layer=layer)

    # ---- prefix layers (unrolled) ----
    for i, spec in enumerate(plan.prefix):
        cache = caches["prefix"][i] if caches is not None else None
        x, nc, aux = run(params["prefix"][i], spec, x, cache)
        new_caches["prefix"].append(nc)
        aux_total = aux_total + aux.get("load_balance_loss", 0.0)
        rows = rows + aux.get("moe_rows", 0)

    # ---- scanned periods ----
    if plan.n_repeats:
        period = plan.period
        # Attention pools ride the carry, stacked over the layers: each
        # layer scatters its new KV into the pool in place and the kernels
        # read it at the layer index. As scan outputs they would be fresh
        # buffers, zero-filled before the loop and copied whole into the
        # returned caches after it. SSM states (small, batch-indexed) and
        # the no-cache path stay inputs and outputs.
        carried = tuple(caches is not None
                        and spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA)
                        for spec in period)
        scan_in = (caches["scan"] if caches is not None
                   else [{} for _ in period])

        def body(carry, xs):
            x, aux_acc, rows_acc, pools = carry
            lps, cs = xs[:2]
            new_cs, new_pools = [], []
            for j, spec in enumerate(period):
                if carried[j]:
                    x, nc, aux = run(lps[j], spec, x, pools[j], layer=xs[2])
                    new_pools.append(nc)
                    nc = {}
                else:
                    cache_j = cs[j] if caches is not None else None
                    x, nc, aux = run(lps[j], spec, x, cache_j)
                    new_pools.append({})
                new_cs.append(nc)
                aux_acc = aux_acc + aux.get("load_balance_loss", 0.0)
                rows_acc = rows_acc + aux.get("moe_rows", 0)
            return (x, aux_acc, rows_acc, tuple(new_pools)), tuple(new_cs)

        if remat:
            body = jax.checkpoint(body)

        pools = tuple(c if k else {} for c, k in zip(scan_in, carried))
        xs = (tuple(params["scan"]),
              tuple({} if k else c for c, k in zip(scan_in, carried)))
        if any(carried):
            xs += (jnp.arange(plan.n_repeats, dtype=jnp.int32),)
        (x, aux_total, rows, pools), scan_caches = jax.lax.scan(
            body, (x, aux_total, rows, pools), xs)
        new_caches["scan"] = [p if k else c for p, c, k
                              in zip(pools, scan_caches, carried)]

    if last_only:
        # serving prefill: only the last position's logits are consumed;
        # skipping the [B, T, vocab] unembed is a large memory/compute win
        x = x[:, -1:]
    hidden = x  # pre-final-norm features (EAGLE-style heads condition on these)
    x = norm(params["final_norm"], x)
    logits = L.unembed_apply(params["embed"], x, cfg)
    # sharded serving: vocab-sharded logits feed softmax/argmax whose
    # distributed reductions would break bitwise cross-mesh identity —
    # all-gather them here in BOTH serving rulesets (sampling always runs
    # on full logits; no-op without an activation mesh, DESIGN.md §11/§13)
    from ..kernels import ops
    logits = ops.gather_activation(logits)
    return logits, (new_caches if caches is not None else None), \
        {"load_balance_loss": aux_total, "hidden": hidden, "moe_rows": rows}
