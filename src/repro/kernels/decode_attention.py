"""Speculative-verify / decode attention — Pallas TPU kernel.

The PARD serving hot path: a small query block (1 AR token or the K+1
verification window) attends to a long KV cache. This is the kernel the
paper's Table 6 bandwidth argument lives in: per iteration the draft+target
weights stream once, and the KV cache stream dominates — so the kernel's job
is to keep the cache read sequential and do the online softmax in VMEM.

The cache is a pool of fixed-size blocks ``[NB, block, Hkv, D]`` plus a
per-row block table ``[B, MBS]`` (the serving layout, serving/kv_pool.py).
A contiguous ``[B, S, Hkv, D]`` cache is the same thing with an implicit
table: ``as_pages`` reshapes it into ``[B * S/block, block, Hkv, D]`` (free:
row-major contiguous) and numbers the blocks in order, so ONE pallas_call
serves both layouts.

Grid: (batch, table entry). Each grid step DMAs one whole block — every KV
head of ``block`` slots — so the K/V block ``(1, block, Hkv, D)`` spans the
array's two minor dims, which is what the TPU tiling rule requires for a
head dim of 64. The queries of one row are regrouped to ``[Hkv, Tq*G, D]``
(kv head major; row ``i*G + j`` = query position ``i``, group member ``j``)
and stay resident in VMEM across the sweep; a static loop over the kv heads
runs the online softmax per head against its ``[block, D]`` slice.

Per-row scalars (``kv_len``, and the block table) are scalar-prefetched into
SMEM; per-query-row vectors (``q_pos``) arrive pre-expanded to ``[B, Tq*G, 1]``
so their VMEM block spans the full minor dims as well. Blocks past a row's
length are skipped (``pl.when``) and their index map repeats the row's last
live block, so the pipeline issues no DMA for them either: swept bytes track
the actual cache fill, not the allocated max_len.

Validity is PER ROW, so one launch serves the serving engine's fused mixed
batches (DESIGN.md §8): decoding rows sweep their long cache while
prefilling rows' chunks (q_pos = cursor + i, kv_len = cursor + chunk) skip
every block past their short fill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Shared by this module and tree_attention.py
# ---------------------------------------------------------------------------

def as_pages(x, block):
    """View a contiguous cache leaf ``[B, S, ...]`` (S a multiple of
    ``block``) as a block pool ``[B * S/block, block, ...]``."""
    b, s = x.shape[:2]
    return x.reshape((b * (s // block), block) + x.shape[2:])


def identity_tables(b, nblocks):
    """Block table of ``as_pages``: row ``r`` owns blocks ``r*n .. r*n+n-1``."""
    return jnp.arange(b * nblocks, dtype=jnp.int32).reshape(b, nblocks)


def group_queries(q, hkv):
    """[B, Tq, Hq, D] -> [B, Hkv, Tq*G, D]: each kv head's grouped queries
    as one row block (row i*G + j = query position i, group member j)."""
    b, tq, hq, d = q.shape
    g = hq // hkv
    return q.reshape(b, tq, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, tq * g, d)


def ungroup_output(o, tq):
    """Inverse of ``group_queries`` for the kernel output."""
    b, hkv, rows, d = o.shape
    g = rows // tq
    return o.reshape(b, hkv, tq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, tq, hkv * g, d)


def query_rows(x, g):
    """Per-query vector [B, Tq] -> per-kernel-row column [B, Tq*G, 1]."""
    return jnp.repeat(x, g, axis=1)[:, :, None]


def last_block(ki, length, block):
    """Table entry to DMA at grid step ``ki`` for a row of ``length`` live
    slots: entries past the last live block repeat it (no new DMA)."""
    return jnp.minimum(ki, jnp.maximum(length - 1, 0) // block)


def kv_block_specs(hkv, d, block, quant, entry):
    """K, V (and quant scale) BlockSpecs over a ``[NB, block, Hkv, D]`` pool.
    ``entry(bi, ki, *prefetch_refs)`` gives the pool block for a grid step;
    the scalar-prefetched block table is always the first prefetch ref."""
    def kv_map(bi, ki, *refs):
        return (entry(bi, ki, *refs), 0, 0, 0)

    def scale_map(bi, ki, *refs):
        return (entry(bi, ki, *refs), 0, 0)

    specs = [pl.BlockSpec((1, block, hkv, d), kv_map)] * 2
    if quant:
        specs += [pl.BlockSpec((1, block, hkv), scale_map)] * 2
    return specs


def attention_scratch(hkv, rows, d):
    """Running max, running sum and accumulator, per kv head."""
    return [pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, d), jnp.float32)]


def init_scratch(m_s, l_s, acc_s):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def sweep_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_s, l_s, acc_s, mask,
                *, scale, softcap):
    """One KV block of the online softmax for every kv head. ``mask`` is the
    [Tq*G, block] validity of this block (the same for every head)."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)                 # [rows, d]
        k = k_ref[0, :, h, :].astype(jnp.float32)           # [block, d]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if ks_ref is not None:
            # dequant fused into the sweep: the block expands against its
            # per-slot scales right after the DMA, still inside VMEM
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[h] = l_s[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[h] = acc_s[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[h] = m_new


def write_output(o_ref, l_s, acc_s):
    """Normalize; rows that saw no valid key (padding) come out as 0."""
    for h in range(o_ref.shape[1]):
        denom = jnp.where(l_s[h] == 0.0, 1.0, l_s[h])
        o_ref[0, h] = (acc_s[h] / denom).astype(o_ref.dtype)


def split_quant_refs(rest, quant):
    if quant:
        return rest[0], rest[1], rest[2:]
    return None, None, rest


# ---------------------------------------------------------------------------
# Decode / flat verify
# ---------------------------------------------------------------------------

def _kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, k_ref, v_ref, *rest, scale,
            window, softcap, block_k, quant):
    # bt_ref (the scalar-prefetched block table) is consumed only by the
    # BlockSpec index maps
    ks_ref, vs_ref, (o_ref, m_s, l_s, acc_s) = split_quant_refs(rest, quant)
    bi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        init_scratch(m_s, l_s, acc_s)

    kv_len = kvlen_ref[bi]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        qp_rows = qpos_ref[0]                               # [rows, 1]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (qp_rows.shape[0], block_k), 1)
        mask = (k_pos < kv_len) & (k_pos <= qp_rows)
        if window:
            mask &= k_pos > qp_rows - window
        sweep_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_s, l_s, acc_s,
                    mask, scale=scale, softcap=softcap)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        write_output(o_ref, l_s, acc_s)


def decode_attention_paged(q, k_pages, v_pages, block_tables, kv_len, q_pos,
                           *, k_scale=None, v_scale=None, window=0,
                           softcap=0.0, scale=None, interpret=False):
    """Paged-pool decode/verify attention.

    q: [B, Tq, Hq, D]; k_pages, v_pages: [NB, block, Hkv, D] shared pools;
    block_tables: [B, MBS] int32 (block 0 = reserved garbage block);
    kv_len: [B] int32 valid entries; q_pos: [B, Tq] absolute positions.
    k_scale/v_scale: optional [NB, block, Hkv] float32 per-slot dequant
    scales when the pools are quantized (int8 / fp8); the scale blocks
    ride the same table indirection as their pages.
    """
    b, tq, hq, d = q.shape
    block, hkv = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    rows = tq * g
    quant = k_scale is not None
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def entry(bi, ki, bt, kl):
        return bt[bi, last_block(ki, kl[bi], block)]

    def row_map(bi, ki, *_):
        return (bi, 0, 0)

    def head_map(bi, ki, *_):
        return (bi, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, rows, 1), row_map),                # q_pos
                pl.BlockSpec((1, hkv, rows, d), head_map)]          # q
    in_specs += kv_block_specs(hkv, d, block, quant, entry)
    args = [block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
            query_rows(q_pos.astype(jnp.int32), g), group_queries(q, hkv),
            k_pages, v_pages]
    if quant:
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    kern = functools.partial(_kernel, scale=scale, window=window,
                             softcap=softcap, block_k=block, quant=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, block_tables.shape[1]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, rows, d), head_map),
            scratch_shapes=attention_scratch(hkv, rows, d)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*args)
    return ungroup_output(out, tq)


def decode_attention(q, k, v, kv_len, q_pos, *, k_scale=None, v_scale=None,
                     window=0, softcap=0.0, scale=None, block_k=256,
                     interpret=False):
    """q: [B, Tq, Hq, D] (Tq small); k, v: [B, S, Hkv, D] with S a multiple
    of ``block_k``; kv_len: [B] int32 valid cache entries; q_pos: [B, Tq]
    absolute. k_scale/v_scale: optional [B, S, Hkv] float32 dequant scales
    for quantized k/v (int8 / fp8); dequant is fused into the stream."""
    b, s = k.shape[:2]
    quant = k_scale is not None
    return decode_attention_paged(
        q, as_pages(k, block_k), as_pages(v, block_k),
        identity_tables(b, s // block_k), kv_len, q_pos,
        k_scale=as_pages(k_scale, block_k) if quant else None,
        v_scale=as_pages(v_scale, block_k) if quant else None,
        window=window, softcap=softcap, scale=scale, interpret=interpret)


# ---------------------------------------------------------------------------
# Latent (MLA, absorbed) decode / flat verify
# ---------------------------------------------------------------------------

def _latent_kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, kv_ref, o_ref, m_s,
                   l_s, acc_s, *, scale, block_k, rank, width):
    # bt_ref is consumed only by the BlockSpec index maps
    bi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        init_scratch(m_s, l_s, acc_s)

    kv_len = kvlen_ref[bi]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        qp_rows = qpos_ref[0]                               # [rows, 1]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (qp_rows.shape[0], block_k), 1)
        mask = (k_pos < kv_len) & (k_pos <= qp_rows)
        c = kv_ref[0, :, :rank]                             # [block, rank]
        # one page feeds both products: the scores over the whole row
        # (latent part + rope part) and the values from its latent part
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[0, :, :rank], c, dims,
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(q_ref[0, :, rank:], kv_ref[0, :, rank:width],
                                 dims, preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * scale, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        denom = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])
        o_ref[0] = (acc_s[...] / denom).astype(o_ref.dtype)


def paged_latent_attention(q, pages, block_tables, kv_len, q_pos, *, rank,
                           scale, interpret=False):
    """Absorbed multi-head latent attention over a paged latent pool.

    q: [B, Tq, H, W] absorbed queries ``[q_nope W_uk | q_rope]``, W = rank
    + rope width; pages: [NB, block, P] pool rows ``[c_kv | k_rope | pad]``,
    P >= W (a pool stacked over layers viewed as one,
    ``attention.layer_pages``);
    block_tables: [B, MBS] int32; kv_len: [B] int32; q_pos: [B, Tq].
    Returns [B, Tq, H, rank]: softmax(scale * q . row) . c_kv.

    Every query head shares the one latent "head": a row's Tq x H queries
    are one [Tq*H, W] block (row i*H + j = position i, head j), resident in
    VMEM across the sweep. Grid (batch, table entry); each grid step DMAs
    one page, whose whole rows are the keys and whose first ``rank``
    channels are the values. The matmuls take bfloat16 operands with
    float32 accumulation; the online softmax is float32. Pages past a
    row's length are skipped and their DMA elided, as in
    ``decode_attention_paged``."""
    b, tq, h, w = q.shape
    block, pw = pages.shape[1], pages.shape[2]
    rows = tq * h

    def entry(bi, ki, bt, kl):
        return bt[bi, last_block(ki, kl[bi], block)]

    def row_map(bi, ki, *_):
        return (bi, 0, 0)

    def page_map(bi, ki, *refs):
        return (entry(bi, ki, *refs), 0, 0)

    kern = functools.partial(_latent_kernel, scale=scale, block_k=block,
                             rank=rank, width=w)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, block_tables.shape[1]),
            in_specs=[pl.BlockSpec((1, rows, 1), row_map),        # q_pos
                      pl.BlockSpec((1, rows, w), row_map),        # q
                      pl.BlockSpec((1, block, pw), page_map)],    # page
            out_specs=pl.BlockSpec((1, rows, rank), row_map),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        interpret=interpret,
        name="paged_latent_attention",
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
      query_rows(q_pos.astype(jnp.int32), h), q.reshape(b, rows, w), pages)
    return out.reshape(b, tq, h, rank)
