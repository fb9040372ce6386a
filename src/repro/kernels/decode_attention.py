"""Speculative-verify / decode attention — Pallas TPU kernels.

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

The page sweep (``sweep_pages``), shared by the GQA and the latent kernel:
the grid is one step per row. The pools stay in HBM (``pl.ANY``); the row's
live pages, ``ceil(kv_len / block)`` table entries and no more, are copied
into VMEM in groups of ``P`` pages (``group_pages``: the largest power of
two whose group of every operand's pages fits ``GROUP_BYTES``), each page
whole — every kv head of ``block`` slots — in one copy through the
scalar-prefetched table. Groups alternate between the two slots of a VMEM
buffer: while one group is scored, the copies of the next one (or of the
next row's first group) are in flight. A group is scored as ONE
``[rows, P * block]`` block per kv head — one QK and one PV matmul, one
online-softmax update — so the per-step cost of the grid and of the
softmax is paid per group, not per page. A row of ``kv_len`` 0 copies
nothing and writes zeros.

Mosaic copies a page out of an HBM pool only where the page's minor dims
fill whole tiles (``sweep_group``). GQA pools that do not — a quantized
pool (its scales are ``[.., Hkv]`` wide), a head dim of 64 — take one page
a grid step instead, grid (batch, table entry), through BlockSpecs whose
index maps read the table; entries past a row's length repeat its last
live page (``last_block``), so no DMA is issued for them, but each still
costs a grid step. The tree kernel (tree_attention.py) sweeps that way too.

The queries of one row are regrouped to ``[Hkv, Tq*G, D]`` (kv head major;
row ``i*G + j`` = query position ``i``, group member ``j``) and stay
resident in VMEM across the sweep; a static loop over the kv heads runs the
online softmax per head against its ``[P * block, D]`` slice. Per-row
scalars (``kv_len``, and the block table) are scalar-prefetched into SMEM;
per-query-row vectors (``q_pos``) arrive pre-expanded to ``[B, Tq*G, 1]``
so their VMEM block spans the full minor dims.

Validity is PER ROW, so one launch serves the serving engine's fused mixed
batches (DESIGN.md §8): decoding rows sweep their long cache while
prefilling rows' chunks (q_pos = cursor + i, kv_len = cursor + chunk) copy
only the few pages of their short fill.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# A buffer slot's copy budget: a group takes the most pages, a power of
# two, whose bytes over every operand the kernel copies fit in it. On a
# v5e at the served shapes (64-slot pages) groups of 8 pages were faster
# than 4 or 16, both for a bf16 K and V pool of 2 x 128 (64 KiB a page)
# and for a 640-lane latent pool (80 KiB a page) (PERF.md).
GROUP_BYTES = 768 * 1024


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
    """One KV block ``[1, n, Hkv, D]`` (a page, or a group of pages) of the
    online softmax for every kv head. ``mask`` is the [Tq*G, n] validity of
    this block (the same for every head)."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)                 # [rows, d]
        k = k_ref[0, :, h, :].astype(jnp.float32)           # [n, d]
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
# The page sweep, shared by the GQA and the latent kernel
# ---------------------------------------------------------------------------

def page_bytes(pools):
    """Bytes of one table entry over the page pools ``[NB, block, ...]`` a
    kernel copies."""
    return sum(math.prod(p.shape[1:]) * p.dtype.itemsize for p in pools)


def group_pages(nbytes, entries):
    """P, the pages the sweep copies and scores at once: the largest power
    of two with ``P * nbytes <= GROUP_BYTES`` for pages of ``nbytes`` (at
    least 1), capped at the block table's ``entries``."""
    p = 1
    while 2 * p * nbytes <= GROUP_BYTES:
        p *= 2
    return min(p, entries)


def _copyable(pool):
    """Whether a kernel can copy single pages out of ``pool`` [NB, ...] in
    HBM. Mosaic slices an HBM array only where the page's two minor dims
    fill its tiles whole: the minor dim a multiple of 128 lanes, the one
    before it a power of two of at least the rows one sublane packs (2 for
    bf16, 4 for 8-bit types) or a multiple of a full tile (8 sublanes)."""
    if pool.ndim < 3 or pool.shape[-1] % 128:
        return False
    rows, pack = pool.shape[-2], 4 // pool.dtype.itemsize
    return rows % (8 * pack) == 0 or (rows >= pack and rows & (rows - 1) == 0)


def sweep_group(pools, entries):
    """P for pools the page sweep copies itself (``group_pages``), 0 for
    pools it cannot (``_copyable``): those take one page a grid step over
    every table entry, through a BlockSpec."""
    if not all(_copyable(p) for p in pools):
        return 0
    return group_pages(page_bytes(pools), entries)


def sweep_scratch(pools, group):
    """The sweep's scratch: a two-slot VMEM buffer ``[2, group * block,
    ...]`` per pool, a DMA semaphore per slot, and the SMEM slot index."""
    return ([pltpu.VMEM((2, group * p.shape[1]) + p.shape[2:], p.dtype)
             for p in pools]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)])


def sweep_pages(bt_ref, kvlen_ref, pools, bufs, sem, slot_ref, *, group,
                score):
    """Sweep the grid step's row over its live pages, ``group`` at a time
    (module docstring). ``pools``: the HBM page pools ``[NB, block, ...]``
    the table indexes; ``bufs``, ``sem``, ``slot_ref``: ``sweep_scratch``,
    the slot carried from row to row. ``score(slot, k_start)`` scores the
    group held in buffer slot ``slot``, whose first key sits at absolute
    position ``k_start``."""
    bi, n_rows = pl.program_id(0), pl.num_programs(0)
    block, entries = pools[0].shape[1], bt_ref.shape[1]

    def live_pages(r):
        return jnp.minimum(pl.cdiv(kvlen_ref[r], block), entries)

    def each_copy(r, g, slot, act):
        # one copy per live page of the group and pool: a page past the
        # row's kv_len is never copied
        n = live_pages(r)
        for j in range(group):
            e = g * group + j

            @pl.when(e < n)
            def _():
                page = bt_ref[r, e]
                for pool, buf in zip(pools, bufs):
                    act(pltpu.make_async_copy(
                        pool.at[page], buf.at[slot, pl.ds(j * block, block)],
                        sem.at[slot]))

    def start(cp):
        cp.start()

    def wait(cp):
        cp.wait()

    @pl.when(bi == 0)
    def _first_row():
        # a group's dead pages keep what its slot held before, which is
        # masked out; zeroed once here, it is never uninitialised memory,
        # whose NaNs would survive the mask as 0 * NaN in the PV product
        for buf in bufs:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0
        each_copy(0, 0, 0, start)

    n_groups = pl.cdiv(live_pages(bi), group)
    has_next = bi + 1 < n_rows

    @pl.when((n_groups == 0) & has_next)
    def _pass_on():
        each_copy(bi + 1, 0, slot_ref[0], start)

    def step(g, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(g + 1 < n_groups)
        def _():
            each_copy(bi, g + 1, nxt, start)

        @pl.when((g + 1 == n_groups) & has_next)
        def _():
            each_copy(bi + 1, 0, nxt, start)

        each_copy(bi, g, slot, wait)
        score(slot, g * group * block)
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_groups, step, 0)


def _sweep_call(kern, pools, block_tables, group, *, in_specs, out_spec,
                out_shape, scratch, interpret, name):
    """The pallas_call both kernels make: one grid step per row, the block
    table and ``kv_len`` scalar-prefetched, the pools left in HBM."""
    return pl.pallas_call(
        functools.partial(kern, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(block_tables.shape[0],),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * len(
                pools),
            out_specs=out_spec,
            scratch_shapes=sweep_scratch(pools, group) + scratch),
        out_shape=out_shape,
        # rows run in order: each prefetches the next one's first group
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )


def key_mask(qpos_ref, kv_len, k_start, n, window=0):
    """[rows, n] validity of the ``n`` keys from position ``k_start`` for
    each query row: inside the row's fill, causal, and inside the sliding
    ``window`` when one is given."""
    qp_rows = qpos_ref[0]                                   # [rows, 1]
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (qp_rows.shape[0], n), 1)
    mask = (k_pos < kv_len) & (k_pos <= qp_rows)
    if window:
        mask &= k_pos > qp_rows - window
    return mask


# ---------------------------------------------------------------------------
# Decode / flat verify
# ---------------------------------------------------------------------------

def _kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
            v_buf, sem, slot_ref, m_s, l_s, acc_s, *, scale, window, softcap,
            group):
    kv_len = kvlen_ref[pl.program_id(0)]
    init_scratch(m_s, l_s, acc_s)

    def score(slot, k_start):
        mask = key_mask(qpos_ref, kv_len, k_start, k_buf.shape[1], window)
        sweep_block(q_ref, k_buf.at[pl.ds(slot, 1)], v_buf.at[pl.ds(slot, 1)],
                    None, None, m_s, l_s, acc_s, mask, scale=scale,
                    softcap=softcap)

    sweep_pages(bt_ref, kvlen_ref, [k_hbm, v_hbm], [k_buf, v_buf], sem,
                slot_ref, group=group, score=score)
    write_output(o_ref, l_s, acc_s)


def _page_kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                 scale, window, softcap, block_k, quant):
    # one page a grid step through BlockSpecs, for pools the sweep cannot
    # copy itself (``sweep_group``); bt_ref (the scalar-prefetched block
    # table) is consumed only by the BlockSpec index maps
    ks_ref, vs_ref, (o_ref, m_s, l_s, acc_s) = split_quant_refs(rest, quant)
    bi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        init_scratch(m_s, l_s, acc_s)

    kv_len = kvlen_ref[bi]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        mask = key_mask(qpos_ref, kv_len, k_start, block_k, window)
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

    The page sweep serves pools whose pages it can copy (``sweep_group``):
    bf16 and float32 pools at a head dim of 128. Others (a quantized pool,
    whose scales are [.., Hkv] wide; a head dim of 64) take one page a
    grid step over every table entry through BlockSpecs; entries past a
    row's length repeat its last live page, so no DMA is issued for them.
    """
    b, tq, hq, d = q.shape
    block, hkv = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    rows = tq * g
    quant = k_scale is not None
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    pools = [k_pages, v_pages]
    if quant:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    group = sweep_group(pools, block_tables.shape[1])

    def row_map(bi, *_):
        return (bi, 0, 0)

    def head_map(bi, *_):
        return (bi, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, rows, 1), row_map),                # q_pos
                pl.BlockSpec((1, hkv, rows, d), head_map)]          # q
    out_spec = pl.BlockSpec((1, hkv, rows, d), head_map)
    out_shape = jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype)
    args = [block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
            query_rows(q_pos.astype(jnp.int32), g), group_queries(q, hkv),
            *pools]
    if group:
        out = _sweep_call(
            functools.partial(_kernel, scale=scale, window=window,
                              softcap=softcap),
            pools, block_tables, group, in_specs=in_specs, out_spec=out_spec,
            out_shape=out_shape, scratch=attention_scratch(hkv, rows, d),
            interpret=interpret, name="paged_decode_attention")(*args)
        return ungroup_output(out, tq)

    def entry(bi, ki, bt, kl):
        return bt[bi, last_block(ki, kl[bi], block)]

    kern = functools.partial(_page_kernel, scale=scale, window=window,
                             softcap=softcap, block_k=block, quant=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, block_tables.shape[1]),
            in_specs=in_specs + kv_block_specs(hkv, d, block, quant, entry),
            out_specs=out_spec,
            scratch_shapes=attention_scratch(hkv, rows, d)),
        out_shape=out_shape,
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

def _latent_kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, pool_ref, o_ref, buf,
                   sem, slot_ref, m_s, l_s, acc_s, *, scale, rank, width,
                   group):
    kv_len = kvlen_ref[pl.program_id(0)]
    init_scratch(m_s, l_s, acc_s)

    def score(slot, k_start):
        mask = key_mask(qpos_ref, kv_len, k_start, buf.shape[1])
        kv = buf.at[slot]                                   # [n, lanes]
        c = kv[:, :rank]                                    # [n, rank]
        # one group feeds both products: the scores over the whole row
        # (latent part + rope part) and the values from its latent part
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[0, :, :rank], c, dims,
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(q_ref[0, :, rank:], kv[:, rank:width],
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

    sweep_pages(bt_ref, kvlen_ref, [pool_ref], [buf], sem, slot_ref,
                group=group, score=score)
    denom = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])
    o_ref[0] = (acc_s[...] / denom).astype(o_ref.dtype)


def paged_latent_attention(q, pages, block_tables, kv_len, q_pos, *, rank,
                           scale, interpret=False):
    """Absorbed multi-head latent attention over a paged latent pool.

    q: [B, Tq, H, W] absorbed queries ``[q_nope W_uk | q_rope]``, W = rank
    + rope width; pages: [NB, block, lanes] pool rows ``[c_kv | k_rope |
    pad]``, lanes >= W (a pool stacked over layers viewed as one,
    ``attention.layer_pages``);
    block_tables: [B, MBS] int32; kv_len: [B] int32; q_pos: [B, Tq].
    Returns [B, Tq, H, rank]: softmax(scale * q . row) . c_kv.

    Every query head shares the one latent "head": a row's Tq x H queries
    are one [Tq*H, W] block (row i*H + j = position i, head j), resident in
    VMEM across the sweep. The page sweep copies the row's live pages a
    group at a time; a group's whole rows are the keys and their first
    ``rank`` channels the values. The matmuls take bfloat16 operands with
    float32 accumulation; the online softmax is float32."""
    b, tq, h, w = q.shape
    rows = tq * h

    def row_map(bi, *_):
        return (bi, 0, 0)

    kern = functools.partial(_latent_kernel, scale=scale, rank=rank, width=w)
    out = _sweep_call(
        kern, [pages], block_tables,
        group_pages(page_bytes([pages]), block_tables.shape[1]),
        in_specs=[pl.BlockSpec((1, rows, 1), row_map),              # q_pos
                  pl.BlockSpec((1, rows, w), row_map)],             # q
        out_spec=pl.BlockSpec((1, rows, rank), row_map),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        scratch=[pltpu.VMEM((rows, 1), jnp.float32),
                 pltpu.VMEM((rows, 1), jnp.float32),
                 pltpu.VMEM((rows, rank), jnp.float32)],
        interpret=interpret, name="paged_latent_attention",
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
      query_rows(q_pos.astype(jnp.int32), h), q.reshape(b, rows, w), pages)
    return out.reshape(b, tq, h, rank)
