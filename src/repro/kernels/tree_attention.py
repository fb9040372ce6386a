"""Tree-verification attention — Pallas TPU kernel.

Speculative *tree* verification (DESIGN.md §6): one target forward scores a
packed candidate tree of draft tokens instead of a single chain. The query
block holds the verify window ``[root | tree nodes]`` (root = re-processed
last committed token); its KV is written at consecutive cache slots
``win_start .. win_start + Tq - 1`` even though nodes on different branches
share logical (RoPE) positions. Plain causal masking is therefore wrong
inside the window — node i may only attend its ancestors — so the kernel
carries a packed per-query ancestor bitmask alongside the causal rule:

  * cache slot  < win_start             -> committed context: always allowed
    (optionally sliding-window limited against the query's logical position);
  * cache slot == win_start + j (j<Tq)  -> allowed iff bit j of ``anc[row]``
    is set (bit 0 = root; a node's mask is its parent's mask | its own bit)
    AND j < the row's ``win_len`` (per-request tree templates pad the batch
    window to the widest template; slots past a row's own template are
    meaningless and invisible);
  * everything is bounded by ``kv_index < min(kv_len, win_start + win_len)``
    — the per-row effective length, so a narrow-template row's KV sweep
    skips the padded window blocks entirely (swept bytes track the row's
    OWN tree, not the bank's widest).

Window sizes are <= 32 slots, so one uint32 bitmask per query row packs the
whole tree. Ancestors sit at most ``max_depth`` logical positions behind the
query, far inside any realistic sliding window, so the window test applies
to context keys only.

The grid, block layout, query grouping and the online-softmax sweep are the
decode kernel's (kernels/decode_attention.py): one pallas_call over the
block pool serves both cache layouts, per-row scalars (``kv_len``,
``win_start``, ``win_len``) are scalar-prefetched, and the per-query
``q_pos``/``anc`` vectors arrive pre-expanded to kernel rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (as_pages, attention_scratch, group_queries,
                               identity_tables, init_scratch, kv_block_specs,
                               last_block, query_rows, split_quant_refs,
                               sweep_block, ungroup_output, write_output)


def _kernel(bt_ref, kvlen_ref, winstart_ref, winlen_ref, qpos_ref, anc_ref,
            q_ref, k_ref, v_ref, *rest, scale, window, softcap, block_k, tq,
            quant):
    ks_ref, vs_ref, (o_ref, m_s, l_s, acc_s) = split_quant_refs(rest, quant)
    bi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        init_scratch(m_s, l_s, acc_s)

    ws = winstart_ref[bi]
    wl = winlen_ref[bi]                                # row's own window
    eff_len = jnp.minimum(kvlen_ref[bi], ws + wl)      # per-row sweep bound
    k_start = ki * block_k

    @pl.when(k_start < eff_len)
    def _compute():
        qp_rows = qpos_ref[0]                          # [rows, 1] int32
        anc_rows = anc_ref[0]                          # [rows, 1] uint32
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (qp_rows.shape[0], block_k), 1)
        ctx = k_pos < ws                               # committed context
        if window:
            ctx &= k_pos > qp_rows - window
        j = k_pos - ws                                 # window slot index
        in_win = (j >= 0) & (j < wl) & (j < tq)
        bit = (anc_rows >> jnp.clip(j, 0, tq - 1).astype(jnp.uint32)
               ) & jnp.uint32(1)
        mask = (k_pos < eff_len) & (ctx | (in_win & (bit == 1)))
        sweep_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_s, l_s, acc_s,
                    mask, scale=scale, softcap=softcap)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        write_output(o_ref, l_s, acc_s)


def tree_attention_paged(q, k_pages, v_pages, block_tables, kv_len, q_pos,
                         win_start, anc, *, win_len=None, k_scale=None,
                         v_scale=None, window=0, softcap=0.0, scale=None,
                         interpret=False):
    """Paged-pool tree-verification attention.

    q: [B, Tq, Hq, D]; k_pages, v_pages: [NB, block, Hkv, D] shared pools;
    block_tables: [B, MBS] int32 (block 0 = reserved garbage block);
    kv_len: [B]; q_pos: [B, Tq] logical positions; win_start: [B];
    anc: [B, Tq] uint32 ancestor bitmasks; win_len: [B] int32 meaningful
    window slots per row (None = Tq); k_scale/v_scale: optional
    [NB, block, Hkv] float32 per-slot dequant scales when the pools are
    quantized (int8 / fp8) — they ride the same table indirection.
    """
    b, tq, hq, d = q.shape
    block, hkv = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    rows = tq * g
    quant = k_scale is not None
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if win_len is None:
        win_len = jnp.full((b,), tq, jnp.int32)

    def entry(bi, ki, bt, kl, ws, wl):
        return bt[bi, last_block(ki, jnp.minimum(kl[bi], ws[bi] + wl[bi]),
                                 block)]

    def row_map(bi, ki, *_):
        return (bi, 0, 0)

    def head_map(bi, ki, *_):
        return (bi, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, rows, 1), row_map),                # q_pos
                pl.BlockSpec((1, rows, 1), row_map),                # anc
                pl.BlockSpec((1, hkv, rows, d), head_map)]          # q
    in_specs += kv_block_specs(hkv, d, block, quant, entry)
    args = [block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
            win_start.astype(jnp.int32), win_len.astype(jnp.int32),
            query_rows(q_pos.astype(jnp.int32), g),
            query_rows(anc.astype(jnp.uint32), g), group_queries(q, hkv),
            k_pages, v_pages]
    if quant:
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    kern = functools.partial(_kernel, scale=scale, window=window,
                             softcap=softcap, block_k=block, tq=tq,
                             quant=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, block_tables.shape[1]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, rows, d), head_map),
            scratch_shapes=attention_scratch(hkv, rows, d)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_tree_attention",
    )(*args)
    return ungroup_output(out, tq)


def tree_attention(q, k, v, kv_len, q_pos, win_start, anc, *, win_len=None,
                   k_scale=None, v_scale=None, window=0, softcap=0.0,
                   scale=None, block_k=256, interpret=False):
    """q: [B, Tq, Hq, D] — the packed verify window; k, v: [B, S, Hkv, D]
    with S a multiple of ``block_k``; kv_len: [B]; q_pos: [B, Tq] logical
    positions (root pos + depth); win_start: [B] cache index of window slot
    0; anc: [B, Tq] uint32 ancestor bitmasks (bit j = window slot j
    visible); win_len: [B] int32 count of meaningful window slots per row
    (None = Tq for every row — single-template batches); k_scale/v_scale:
    optional [B, S, Hkv] float32 dequant scales for quantized k/v."""
    b, s = k.shape[:2]
    quant = k_scale is not None
    return tree_attention_paged(
        q, as_pages(k, block_k), as_pages(v, block_k),
        identity_tables(b, s // block_k), kv_len, q_pos, win_start, anc,
        win_len=win_len,
        k_scale=as_pages(k_scale, block_k) if quant else None,
        v_scale=as_pages(v_scale, block_k) if quant else None,
        window=window, softcap=softcap, scale=scale, interpret=interpret)
