"""Attention blocks: GQA (global / sliding-window), MLA, cross-attention.

All flavours share one masked-attention core (``attend``) driven by absolute
positions, so training (no cache), prefill (cache write, full seq) and decode
(small q against a long cache) are the same code path. The PARD training
mask (Fig. 4/5 of the paper) enters through ``mask_info`` — per-token
(segment, base) metadata — and is computed functionally, never materialised
by the caller.

KV caches are block-paged pools (DESIGN.md §5): fixed-size KV blocks in a
shared pool ``[num_blocks, block, ...]`` with a per-row block table
``[B, max_blocks]`` mapping absolute position ``p`` to
``(table[b, p // block], p % block)``. Block 0 is the reserved garbage
block: unallocated table entries point at it, so writes past a row's
allocation land there and are never attended (reads are bounded by
``kv_len``). Speculative rollback is just resetting ``cache_pos``: stale
entries are masked out by the validity test ``kv_index < kv_len``. Inside
the layer loop a scanned layer's pool stays stacked over the layers,
``[L, num_blocks, block, ...]``, and the paged functions take the
``layer`` to write and read (models/transformer.py ``forward``). The
serving allocator lives in serving/kv_pool.py.

Chunked prefill (DESIGN.md §8) needs nothing new here: every path is
driven by PER-ROW ``cache_pos``/positions, so one forward mixes decoding
rows (small verify window against a long cache) with prefilling rows (a
prompt chunk at the row's cursor) — the serving executor's fused step is
just such a batch. Under tree verification a prefilling row carries a
causal all-lower-bits ancestor mask and ``win_len`` = its chunk's real
token count, making the chunk an ordinary causal window to
``tree_allowed`` and both tree kernels.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import scopes
from .layers import apply_rope, softcap, yarn_mscale

Array = jax.Array

NEG_INF = -1e30

# attention backend: "xla" (the jnp reference) or "pallas" (the kernels/
# implementations). set_attention_backend pins one for tests (interpret-
# mode kernels on the CPU — the tests assert both paths agree); None, the
# default, lets the platform choose (_use_pallas).
_BACKEND = None


def set_attention_backend(name) -> None:
    global _BACKEND
    assert name in (None, "xla", "pallas")
    _BACKEND = name


def _use_pallas(cached: bool = True) -> bool:
    """Whether attention runs the Pallas kernels. Unpinned, a TPU runs the
    compiled kernels on the CACHED paths — the serving steps' prefill
    chunks, decode and verify windows — and every other backend the jnp
    reference. An uncached forward (training, whose loss is
    differentiated; scoring) keeps the jnp path on a TPU too: the kernels
    define no VJP, and flash_attention is not partitioned under a training
    mesh."""
    if _BACKEND is None:
        return cached and jax.default_backend() == "tpu"
    return _BACKEND == "pallas"


def _pallas_ok(q, k, v, mask_info, *, cached: bool = True) -> bool:
    """The Pallas kernels cover the standard GQA cases: no PARD metadata
    (training masks use ops.pard_attention via the loss path) and one head
    dim for q, k and v (MLA's value dim differs: the xla path)."""
    return (_use_pallas(cached) and mask_info is None
            and q.shape[-1] == k.shape[-1] == v.shape[-1])


# ---------------------------------------------------------------------------
# Quantized KV storage (DESIGN.md §10)
# ---------------------------------------------------------------------------

# names accepted by Engine(kv_dtype=) / --kv-dtype. "bf16" keeps the
# historical unquantized layout byte-for-byte; int8/fp8 store each KV vector
# quantized against a per-(position, head) float32 scale carried in sibling
# "*_scale" cache leaves (quantize at append, dequantize at read — fused
# into the Pallas streaming bodies on the pallas backend).
KV_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp32": jnp.float32,
    "int8": jnp.int8,
    "fp8": jnp.float8_e4m3fn,
}

# largest representable magnitude per quantized storage dtype: one scale
# unit maps amax onto it
_QUANT_MAXVAL = {jnp.dtype(jnp.int8): 127.0,
                 jnp.dtype(jnp.float8_e4m3fn): 448.0}


def resolve_kv_dtype(kv_dtype):
    """Accept a KV_DTYPES name or any dtype; return the storage dtype."""
    if isinstance(kv_dtype, str):
        return jnp.dtype(KV_DTYPES[kv_dtype])
    return jnp.dtype(kv_dtype)


def kv_dtype_is_quantized(dtype) -> bool:
    return jnp.dtype(dtype) in _QUANT_MAXVAL


def quantize_kv(x, qdtype):
    """Per-(position, head) symmetric quantization over the trailing axis.

    x: [..., D] -> (q [..., D] qdtype, scale [...] float32) such that
    ``dequantize_kv(q, scale)`` reconstructs x. int8 scales are amax/127
    with round+clip; fp8 (e4m3) scales are amax/448 with the cast doing the
    mantissa rounding. All-zero vectors take scale 1 so the garbage block's
    zeros stay exactly zero, and a scale is never 0 (dequant never NaNs).
    """
    maxval = _QUANT_MAXVAL[jnp.dtype(qdtype)]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    # floored at the smallest normal float: amax / maxval of a tiny row
    # would be subnormal, which the backend may flush to a zero scale
    scale = jnp.where(amax > 0,
                      jnp.maximum(amax / maxval, jnp.finfo(jnp.float32).tiny),
                      1.0)
    scaled = xf / scale[..., None]
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(scaled), -maxval, maxval).astype(jnp.int8)
    else:
        q = scaled.astype(qdtype)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of quantize_kv: [..., D] values x [...] scales -> float32."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


class PardMaskInfo(NamedTuple):
    """Per-token PARD-COD metadata (see core/cod.py).

    segment[i] = s >= 1: which prediction subtask the token belongs to
                 (s==1: real tokens; s>=2: mask tokens predicting the s-th
                 next token).
    base[i]    = n: context length the token conditions on. For s==1 tokens
                 base == position == index in the original sequence.
    A query (s_q, n_q) may attend key (s_k, n_k) iff:
      s_k == 1 and n_k <  n_q              (real context)
      s_k  > 1 and s_k <  s_q and n_k == n_q   (earlier masks, same base)
      s_k == s_q and n_k == n_q            (self)
    Padding tokens carry segment == 0 and never attend / are attended.
    """
    segment: Array  # [B, T] int32
    base: Array     # [B, T] int32


class TreeAttnInfo(NamedTuple):
    """Packed candidate-tree metadata for speculative tree verification
    (DESIGN.md §6). The verify window's KV occupies consecutive cache slots
    ``win_start .. win_start + Tq - 1`` even though sibling branches share
    logical positions, so within-window visibility is an ancestor relation,
    not a positional one.

    win_start: [B] int32 — cache index of window slot 0 (the re-processed
               last committed token; == the verify forward's ``cache_pos``).
               Cache entries below it are committed context, always visible.
    anc:       [B, Tq] uint32 — per query slot s, bit j set iff window slot
               j is an ancestor-or-self of s (bit 0 = the root). Windows are
               <= 32 slots, so one uint32 packs the whole tree.
    win_len:   [B] int32 (optional) — per-row count of MEANINGFUL window
               slots. With per-request tree templates (DESIGN.md §7) the
               batch window is padded to the bank's widest template; slots
               >= win_len belong to no template node, are never accepted,
               and are masked out of visibility entirely — the Pallas
               kernels additionally clamp each row's KV sweep to
               ``win_start + win_len``, so narrow-template rows stream
               fewer bytes. None = every slot meaningful (single template).
    """
    win_start: Array
    anc: Array
    win_len: Optional[Array] = None


def tree_allowed(q_pos, kv_pos, tree_info: TreeAttnInfo, window=0):
    """Boolean [B, Tq, Tk] visibility under tree verification. Context keys
    (cache index < win_start) obey the optional sliding window against the
    query's *logical* position; window keys obey the ancestor bitmask
    (ancestors are <= max_depth logical positions back — inside any
    realistic sliding window, so the window test applies to context only)."""
    tq = q_pos.shape[1]
    ws = tree_info.win_start.astype(jnp.int32)[:, None, None]    # [B,1,1]
    kvp = kv_pos[:, None, :]                                     # [B,1,Tk]
    ctx = kvp < ws
    if window:
        ctx &= kvp > (q_pos[:, :, None] - window)
    j = kvp - ws
    wl = tq if tree_info.win_len is None \
        else tree_info.win_len.astype(jnp.int32)[:, None, None]
    in_win = (j >= 0) & (j < wl) & (j < tq)
    bits = (tree_info.anc.astype(jnp.uint32)[:, :, None]
            >> jnp.clip(j, 0, tq - 1).astype(jnp.uint32)) & jnp.uint32(1)
    return ctx | (in_win & (bits == 1))


def pard_mask(q_seg, q_base, k_seg, k_base):
    """Boolean [.., Tq, Tk] PARD training mask from metadata (broadcasts)."""
    qs, qb = q_seg[..., :, None], q_base[..., :, None]
    ks, kb = k_seg[..., None, :], k_base[..., None, :]
    real_ctx = (ks == 1) & (kb < qb)
    mask_chain = (ks > 1) & (ks < qs) & (kb == qb)
    self_tok = (ks == qs) & (kb == qb)
    valid = (qs > 0) & (ks > 0)
    return valid & (real_ctx | mask_chain | self_tok)


def attend(q, k, v, q_pos, kv_pos, kv_len, *, causal=True, window=0,
           attn_softcap=0.0, scale=None, mask_info=None, kv_mask_info=None,
           tree_info=None, k_scale=None, v_scale=None):
    """Masked multi-head attention: the Pallas kernels where ``_pallas_ok``
    picks them, else the jnp reference (``attend_reference``).

    Either an uncached forward (training, scoring, the encoder: k/v are
    the forward's own keys) or a cached window against a row's pool pages
    gathered into a per-row view — ``_paged_attend`` and ``mla_apply``
    where the paged kernels do not apply. Only that gathered view reaches
    the contiguous-operand kernel wrappers (``ops.decode_attention`` /
    ``ops.tree_attention``).

    q:      [B, Tq, Hq, Dk]
    k, v:   [B, Tk, Hkv, Dk] / [B, Tk, Hkv, Dv]
    q_pos:  [B, Tq] absolute positions of queries
    kv_pos: [B, Tk] absolute positions of keys
    kv_len: [B] or scalar — number of valid cache entries (Tk used)
    tree_info: optional TreeAttnInfo — tree-verification masking (ancestor
            bitmask inside the window, plain context visibility before it)
            replacing the causal rule for the speculative verify window
    k_scale, v_scale: optional [B, Tk, Hkv] per-(position, head) dequant
            scales for quantized k/v (DESIGN.md §10). The decode/tree Pallas
            kernels fuse the dequant into their KV stream; every other path
            dequantizes up front (the reference semantics).
    """
    b, tq = q.shape[:2]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # q spanning every key is an uncached forward (training, scoring); a
    # shorter q is a prefill chunk, decode or verify window against a cache
    full = tq == k.shape[1]
    kernel = _pallas_ok(q, k, v, mask_info, cached=not full)
    fused_dequant = kernel and (tree_info is not None or (causal and not full))
    if k_scale is not None and not fused_dequant:
        k = dequantize_kv(k, k_scale)
        v = dequantize_kv(v, v_scale)
        k_scale = v_scale = None

    if kernel and (tree_info is not None or causal):
        from ..kernels import ops
        kv_len_arr = jnp.broadcast_to(jnp.asarray(kv_len), (b,)).astype(jnp.int32)
        if tree_info is not None:
            return ops.tree_attention(q, k, v, kv_len_arr, q_pos,
                                      tree_info.win_start, tree_info.anc,
                                      win_len=tree_info.win_len,
                                      k_scale=k_scale, v_scale=v_scale,
                                      window=window, softcap=attn_softcap,
                                      scale=scale)
        if full:
            return ops.flash_attention(q, k, v, causal=True, window=window,
                                       softcap=attn_softcap, scale=scale)
        return ops.decode_attention(q, k, v, kv_len_arr, q_pos,
                                    k_scale=k_scale, v_scale=v_scale,
                                    window=window, softcap=attn_softcap,
                                    scale=scale)
    return attend_reference(q, k, v, q_pos, kv_pos, kv_len, causal=causal,
                            window=window, attn_softcap=attn_softcap,
                            scale=scale, mask_info=mask_info,
                            kv_mask_info=kv_mask_info, tree_info=tree_info)


def attend_reference(q, k, v, q_pos, kv_pos, kv_len, *, causal=True,
                     window=0, attn_softcap=0.0, scale=None, mask_info=None,
                     kv_mask_info=None, tree_info=None):
    """The jnp attention core, whatever the backend: ``attend``'s
    semantics on unquantized k/v, and the oracle of every attention kernel
    (kernels/ref.py)."""
    b, tq, hq, dk = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(dk)

    qg = q.reshape(b, tq, hkv, g, dk)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = softcap(logits, attn_softcap)

    if mask_info is not None:
        allowed = pard_mask(mask_info.segment, mask_info.base,
                            (kv_mask_info or mask_info).segment,
                            (kv_mask_info or mask_info).base)      # [B,Tq,Tk]
    elif tree_info is not None:
        allowed = tree_allowed(q_pos, kv_pos, tree_info, window=window)
    else:
        allowed = jnp.ones((b, tq, k.shape[1]), bool)
        if causal:
            allowed &= kv_pos[:, None, :] <= q_pos[:, :, None]
        if window:
            allowed &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 0:
        kv_len = jnp.full((b,), kv_len)
    valid = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]       # [B,Tk]
    allowed &= valid[:, None, :]

    logits = jnp.where(allowed[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no allowed key (padding queries) produce ~uniform probs over
    # masked keys; their output is garbage but they are never read.
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, tq, hq, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def init_gqa(key, cfg, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense(k1, (d, hq, hd), d),
        "wk": _dense(k2, (d, hkv, hd), d),
        "wv": _dense(k3, (d, hkv, hd), d),
        "wo": _dense(k4, (hq, hd, d), hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), jnp.float32)
        p["bk"] = jnp.zeros((hkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((hkv, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_gqa_pool(cfg, num_blocks, block_size, dtype=jnp.bfloat16):
    """One GQA layer's KV pool: ``k``/``v`` ``[NB, bs, Hkv, hd]``.
    Quantized dtypes add per-(slot, head) dequant scales that ride through
    the same block-table indirection (DESIGN.md §10); scale 1 keeps the
    garbage block dequantizing to exact zeros."""
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    c = {"k": jnp.zeros((num_blocks, block_size, hkv, hd), dtype),
         "v": jnp.zeros((num_blocks, block_size, hkv, hd), dtype)}
    if kv_dtype_is_quantized(dtype):
        c["k_scale"] = jnp.ones((num_blocks, block_size, hkv), jnp.float32)
        c["v_scale"] = jnp.ones((num_blocks, block_size, hkv), jnp.float32)
    return c


def write_cache_paged(pages, new, cache_pos, block_tables, block_size,
                      layer=None):
    """Scatter new KV into a block-paged pool through per-row block tables.

    pages: [NB, bs, ...], or with ``layer`` (an int32 scalar, may be traced)
    a pool stacked over layers [L, NB, bs, ...] written at that layer in
    place; new: [B, T, ...]; cache_pos: [B] int32; block_tables: [B, MBS]
    int32. Rows own disjoint blocks, so the scatter indices never collide
    across the batch; positions mapping past a row's table (or to
    unallocated entries) land in the reserved garbage block 0, whose
    contents are never attended.
    """
    t = new.shape[1]
    at = paged_index(block_tables, cache_pos[:, None] + jnp.arange(t)[None, :],
                     block_size, new.shape[2] if new.ndim == 4 else None)
    if layer is not None:
        at = (layer,) + at
    return pages.at[at].set(new.astype(pages.dtype))


def paged_index(block_tables, pos, block_size, heads=None):
    """Index into a paged pool leaf [NB, bs, ...] for absolute positions
    [B, T] through the per-row block tables: (block, slot) and, given
    ``heads`` (a K/V leaf's kv-head count), the kv head, so that a scatter's
    window is the minor axis alone. An 8-bit pool's default TPU layout puts
    its small kv-head axis outside the slot axis: a scatter that merges
    block and slot, or whose window spans (head, dim), has the whole pool
    relaid out around it. Positions past a row's table resolve to the
    reserved garbage block 0 (never attended: reads are bounded by kv_len)."""
    ent = pos // block_size
    mbs = block_tables.shape[1]
    blk = jnp.take_along_axis(block_tables, jnp.clip(ent, 0, mbs - 1),
                              axis=1)                            # [B, T]
    blk = jnp.where(ent >= mbs, 0, blk)      # past the table -> garbage block
    off = pos % block_size
    if heads is None:
        return blk, off
    return blk[..., None], off[..., None], jnp.arange(heads)


def gather_pages(pages, block_tables):
    """Per-row view of a paged pool (the reference read path).

    pages: [NB, bs, ...]; block_tables: [B, MBS] -> [B, MBS * bs, ...].
    """
    g = jnp.take(pages, block_tables, axis=0)                    # [B, MBS, bs, ...]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def layer_pages(pages, block_tables, layer):
    """One layer of a pool stacked over layers, ``[L, NB, bs, ...]``, read
    as a single-layer pool: the stacked pool viewed as ``[L * NB, bs, ...]``
    (merging its two major axes moves no data) through block tables offset
    to that layer's blocks. Slicing the layer out instead would copy it.
    With ``layer=None`` the pool is one layer's already. Each layer's
    garbage block is its own block 0. Returns (pages, block_tables)."""
    if layer is None:
        return pages, block_tables
    return (pages.reshape((-1,) + pages.shape[2:]),
            block_tables + layer * pages.shape[1])


def _qk_rmsnorm(x, scale, eps):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps) * scale).astype(x.dtype)


# queries per row in the decode/verify windows stay tiny (<= 2K); above this
# the paged Pallas kernel's q tile would not fit VMEM comfortably and the
# gather-based path is used instead (prefill-sized q blocks).
_PAGED_KERNEL_MAX_TQ = 32


def _paged_attend(q, k_pages, v_pages, block_tables, q_pos, kv_len, *,
                  causal=True, window=0, attn_softcap=0.0, scale=None,
                  tree_info=None, k_scale=None, v_scale=None, layer=None):
    """Attention against a block-paged KV pool (stacked over layers and read
    at ``layer`` when one is given, through ``layer_pages``).

    Uses the Pallas paged decode kernel for small query windows on the
    pallas backend (the kernel's mask is causal, so only when ``causal``);
    otherwise gathers the row's blocks into a per-row view and reuses the
    standard ``attend`` core (semantic reference). The gathered temporary
    holds every row's whole table, so the per-step copy is avoided only
    where the kernel path is active (TPU decode/verify).
    """
    b, tq = q.shape[:2]
    kernel = (causal and tq <= _PAGED_KERNEL_MAX_TQ
              and _pallas_ok(q, k_pages, v_pages, None))
    if kernel and k_scale is not None and layer is not None:
        # the kernels take row-major operands. An 8-bit pool's and its
        # float32 scales' default TPU layouts are not row-major (their small
        # kv-head axis would pad), so the stacked quantized pool handed to a
        # kernel would be relaid out whole before and after the layer loop.
        # Hand it the layer instead: only that slice is relaid out.
        k_pages, v_pages, k_scale, v_scale = (
            x[layer] for x in (k_pages, v_pages, k_scale, v_scale))
        layer = None
    k_pages, tables = layer_pages(k_pages, block_tables, layer)
    v_pages, _ = layer_pages(v_pages, block_tables, layer)
    if k_scale is not None:
        k_scale, _ = layer_pages(k_scale, block_tables, layer)
        v_scale, _ = layer_pages(v_scale, block_tables, layer)
    if kernel:
        from ..kernels import ops
        kv_len_arr = jnp.broadcast_to(jnp.asarray(kv_len), (b,)).astype(jnp.int32)
        if tree_info is not None:
            return ops.tree_attention_paged(
                q, k_pages, v_pages, tables, kv_len_arr, q_pos,
                tree_info.win_start, tree_info.anc,
                win_len=tree_info.win_len, k_scale=k_scale, v_scale=v_scale,
                window=window, softcap=attn_softcap, scale=scale)
        return ops.decode_attention_paged(
            q, k_pages, v_pages, tables, kv_len_arr, q_pos,
            k_scale=k_scale, v_scale=v_scale,
            window=window, softcap=attn_softcap, scale=scale)
    k = gather_pages(k_pages, tables)
    v = gather_pages(v_pages, tables)
    if k_scale is not None:
        k = dequantize_kv(k, gather_pages(k_scale, tables))
        v = dequantize_kv(v, gather_pages(v_scale, tables))
    s = k.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return attend(q, k, v, q_pos, kv_pos, kv_len, causal=causal,
                  window=window, attn_softcap=attn_softcap, scale=scale,
                  tree_info=tree_info)


def gqa_apply(params, cfg, x, positions, *, layer_window=0, cache=None,
              cache_pos=None, mask_info=None, causal=True, use_rope=True,
              block_tables=None, kv_block_size=0, tree_info=None, layer=None):
    """Self attention. Returns (y, new_cache). Without a cache, attention
    over the forward's own keys; with one, ``cache`` holds KV pools written
    and read through ``block_tables`` (``layer``: pools stacked over
    layers, written and read at that layer)."""
    b, t, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _qk_rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = _qk_rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    scale = cfg.attn_scale or None
    if cache is None:
        out = attend(q, k, v, positions, positions, t, causal=causal,
                     window=layer_window, attn_softcap=cfg.attn_softcap,
                     scale=scale, mask_info=mask_info)
        new_cache = None
    else:
        if "k_scale" in cache:
            # quantized pool: quantize on append, so prefill chunks, decode
            # windows and tree-window compaction all produce quantized
            # blocks; the freshly written window reads back through the
            # same dequant path as committed context (DESIGN.md §10)
            k, sk = quantize_kv(k, cache["k"].dtype)
            v, sv = quantize_kv(v, cache["v"].dtype)
            new_cache = {
                "k_scale": write_cache_paged(cache["k_scale"], sk, cache_pos,
                                             block_tables, kv_block_size,
                                             layer),
                "v_scale": write_cache_paged(cache["v_scale"], sv, cache_pos,
                                             block_tables, kv_block_size,
                                             layer)}
        else:
            new_cache = {}
        new_cache["k"] = write_cache_paged(cache["k"], k, cache_pos,
                                           block_tables, kv_block_size, layer)
        new_cache["v"] = write_cache_paged(cache["v"], v, cache_pos,
                                           block_tables, kv_block_size, layer)
        out = _paged_attend(q, new_cache["k"], new_cache["v"], block_tables,
                            positions, cache_pos + t, causal=causal,
                            window=layer_window,
                            attn_softcap=cfg.attn_softcap, scale=scale,
                            tree_info=tree_info,
                            k_scale=new_cache.get("k_scale"),
                            v_scale=new_cache.get("v_scale"), layer=layer)
    # sharded serving seam (DESIGN.md §11/§13): exact ruleset all-gathers
    # the head-sharded context BEFORE the wo contraction (bitwise cross-mesh
    # identity); throughput ruleset contracts it row-parallel at canonical
    # chunk granularity and the post-contraction gather becomes the block's
    # single psum; plain einsum without an activation mesh
    from ..kernels import ops
    y = ops.rowparallel_einsum("bthk,hkd->btd", out,
                               params["wo"].astype(x.dtype),
                               x_axis=-2, w_axis=0)
    return ops.gather_activation(y), new_cache


# ---------------------------------------------------------------------------
# Cross attention (static encoder / image KV)
# ---------------------------------------------------------------------------

def init_cross_attn(key, cfg):
    p = init_gqa(key, cfg)
    if cfg.arch_type == "vlm":  # llama-vision gates cross-attn output
        p["gate"] = jnp.zeros((), jnp.float32)
    return p


def precompute_cross_kv(params, cfg, enc_out):
    """enc_out: [B, S, D] -> static cross KV."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    return {"k": k, "v": v}


def cross_attn_apply(params, cfg, x, enc_out=None, cross_kv=None):
    """Cross attention against encoder/image states. Pass either raw
    ``enc_out`` [B, S, D] (KV computed here) or a precomputed ``cross_kv``
    (decode-time optimisation, see ``precompute_cross_kv``)."""
    b, t, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    if cross_kv is None:
        cross_kv = precompute_cross_kv(params, cfg, enc_out)
    k, v = cross_kv["k"], cross_kv["v"]
    s = k.shape[1]
    pos = jnp.zeros((b, t), jnp.int32)
    kv_pos = jnp.zeros((b, s), jnp.int32)
    out = attend(q, k, v, pos, kv_pos, s, causal=False)
    from ..kernels import ops
    y = ops.rowparallel_einsum("bthk,hkd->btd", out,
                               params["wo"].astype(x.dtype),
                               x_axis=-2, w_axis=0)
    if "gate" in params:
        y = jnp.tanh(params["gate"]).astype(y.dtype) * y
    return ops.gather_activation(y)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def init_mla(key, cfg):
    d, h = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if r_q:
        p["w_dq"] = _dense(ks[0], (d, r_q), d)
        p["q_lora_norm"] = jnp.ones((r_q,), jnp.float32)
        p["w_uq"] = _dense(ks[1], (r_q, h, dn + dr), r_q)
    else:
        p["w_q"] = _dense(ks[1], (d, h, dn + dr), d)
    p["w_dkv"] = _dense(ks[2], (d, r_kv + dr), d)
    p["kv_lora_norm"] = jnp.ones((r_kv,), jnp.float32)
    p["w_uk"] = _dense(ks[3], (r_kv, h, dn), r_kv)
    p["w_uv"] = _dense(ks[4], (r_kv, h, dv), r_kv)
    p["wo"] = _dense(ks[5], (h, dv, d), h * dv)
    return p


def mla_pool_width(cfg) -> int:
    """Lanes of a paged MLA pool row: ``[c_kv | k_rope]`` padded with zeros
    to a multiple of 128. A TPU lays a row-major array out in tiles of 128
    lanes, so the padding is memory it would hold anyway; unpadded, its
    default layout for the pool is not row-major, and a kernel reading the
    pool would have it relaid out whole at every call."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def init_mla_pool(cfg, num_blocks, block_size, dtype=jnp.bfloat16):
    """One MLA layer's latent pool ``ckv`` ``[NB, bs, mla_pool_width]``;
    quantized dtypes add one scale per compressed-KV vector (the latent IS
    the "head")."""
    c = {"ckv": jnp.zeros((num_blocks, block_size, mla_pool_width(cfg)),
                          dtype)}
    if kv_dtype_is_quantized(dtype):
        c["ckv_scale"] = jnp.ones((num_blocks, block_size), jnp.float32)
    return c


def _rms(x, scale, eps):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps) * scale).astype(x.dtype)


def mla_softmax_scale(cfg) -> float:
    """DeepSeek-V2's ``softmax_scale``: (qk_nope + qk_rope)^-1/2, times
    mscale(factor, mscale_all_dim)^2 under YaRN."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def latent_attend_reference(q, pages, block_tables, kv_len, q_pos, *,
                            rank, scale):
    """Absorbed MLA attention against a paged latent pool, in jnp: each
    row's pages gathered into a per-row view (the reference read path).

    q: [B, Tq, H, W] — the absorbed query ``[q_nope W_uk | q_rope]``, W =
    rank + dr; pages: [NB, bs, P >= W] rows ``[c_kv | k_rope | pad]``;
    block_tables: [B, MBS]; kv_len: [B]; q_pos: [B, Tq]. The keys are the
    rows' first W channels, the values their first ``rank``, one "head"
    shared by every query head. Returns [B, Tq, H, rank] in q's dtype."""
    kv = gather_pages(pages, block_tables)[..., :q.shape[-1]].astype(
        jnp.float32)                                            # [B, S, W]
    s = kv.shape[1]
    logits = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32), kv) * scale
    kv_pos = jnp.arange(s)[None, None, :]
    allowed = ((kv_pos <= q_pos[:, :, None])
               & (kv_pos < jnp.asarray(kv_len)[:, None, None]))  # [B,Tq,S]
    logits = jnp.where(allowed[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkr->bqhr", probs, kv[..., :rank])
    return out.astype(q.dtype)


def _latent_attend(q, pages, block_tables, kv_len, q_pos, *, rank, scale):
    """``latent_attend_reference``'s semantics: the Pallas latent kernel
    where the platform runs kernels, else the jnp reference."""
    if _use_pallas():
        from ..kernels import ops
        return ops.paged_latent_attention(q, pages, block_tables, kv_len,
                                          q_pos, rank=rank, scale=scale)
    return latent_attend_reference(q, pages, block_tables, kv_len, q_pos,
                                   rank=rank, scale=scale)


def mla_apply(params, cfg, x, positions, *, cache=None, cache_pos=None,
              mask_info=None, block_tables=None, kv_block_size=0,
              tree_info=None, layer=None):
    b, t, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    scale = mla_softmax_scale(cfg)

    if "w_dq" in params:
        cq = _rms(jnp.einsum("btd,dr->btr", x, params["w_dq"].astype(x.dtype)),
                  params["q_lora_norm"], cfg.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", cq, params["w_uq"].astype(x.dtype))
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["w_q"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, yarn_cfg=cfg)

    ckv_full = jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(x.dtype))
    ckv, k_rope = ckv_full[..., :r_kv], ckv_full[..., r_kv:]
    ckv = _rms(ckv, params["kv_lora_norm"], cfg.norm_eps)
    # rope on the shared key channel (1 "head")
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        yarn_cfg=cfg)[:, :, 0, :]
    compressed = jnp.concatenate([ckv, k_rope], axis=-1)     # [B,T,r_kv+dr]
    if cache is not None:
        # a pool row carries zero lanes past r_kv + dr
        compressed = jnp.pad(compressed, ((0, 0), (0, 0), (
            0, mla_pool_width(cfg) - compressed.shape[-1])))

    from ..kernels import ops
    if (cache is not None and "ckv_scale" not in cache and tree_info is None
            and t <= _PAGED_KERNEL_MAX_TQ):
        # decode / verify / prefill chunk: attention in absorbed form
        # over the latent pages themselves. W_uk folds into the query and
        # W_uv applies after attention, so the scores are
        # [q_nope W_uk | q_rope] . [c_kv | k_rope] and the output
        # softmax . c_kv: one shared "head" of width r_kv + dr, read once,
        # with no per-head K or V built from the pool.
        pages = write_cache_paged(cache["ckv"], compressed, cache_pos,
                                  block_tables, kv_block_size, layer)
        with jax.named_scope(scopes.LATENT_ATTENTION):
            q_lat = jnp.einsum("bthn,rhn->bthr", q_nope,
                               params["w_uk"].astype(x.dtype))
            o_lat = _latent_attend(
                jnp.concatenate([q_lat, q_rope], axis=-1),
                *layer_pages(pages, block_tables, layer), cache_pos + t,
                positions, rank=r_kv, scale=scale)
            out = jnp.einsum("bthr,rhv->bthv", o_lat,
                             params["w_uv"].astype(x.dtype))
        y = ops.rowparallel_einsum("bthk,hkd->btd", out,
                                   params["wo"].astype(x.dtype),
                                   x_axis=-2, w_axis=0)
        return ops.gather_activation(y), {"ckv": pages}

    if cache is not None:
        # otherwise (tree verification, quantized pools, long prefill
        # windows): the compressed KV pages gather into a per-row view,
        # projected to full K/V below as on the uncached path. Quantized
        # pools dequantize at the gather.
        if "ckv_scale" in cache:
            qc, sc = quantize_kv(compressed, cache["ckv"].dtype)
            pages = write_cache_paged(cache["ckv"], qc, cache_pos,
                                      block_tables, kv_block_size, layer)
            spages = write_cache_paged(cache["ckv_scale"], sc, cache_pos,
                                       block_tables, kv_block_size, layer)
            new_cache = {"ckv": pages, "ckv_scale": spages}
            kv_src = dequantize_kv(
                gather_pages(*layer_pages(pages, block_tables, layer)),
                gather_pages(*layer_pages(spages, block_tables, layer))
            ).astype(x.dtype)
        else:
            pages = write_cache_paged(cache["ckv"], compressed, cache_pos,
                                      block_tables, kv_block_size, layer)
            new_cache = {"ckv": pages}
            kv_src = gather_pages(*layer_pages(pages, block_tables, layer))
        s = kv_src.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        kv_len = cache_pos + t
    else:
        new_cache = None
        kv_src = compressed
        kv_pos = positions
        kv_len = t

    ckv_all, k_rope_all = kv_src[..., :r_kv], kv_src[..., r_kv:r_kv + dr]
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_all.astype(x.dtype),
                        params["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv_all.astype(x.dtype),
                   params["w_uv"].astype(x.dtype))
    k_rope_b = jnp.broadcast_to(k_rope_all[:, :, None, :].astype(x.dtype),
                                (b, kv_src.shape[1], h, dr))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)

    out = attend(qfull, k, v, positions, kv_pos, kv_len, causal=True,
                 scale=scale, mask_info=mask_info if cache is None else None,
                 tree_info=tree_info if cache is not None else None)
    y = ops.rowparallel_einsum("bthk,hkd->btd", out,
                               params["wo"].astype(x.dtype),
                               x_axis=-2, w_axis=0)
    return ops.gather_activation(y), new_cache
