"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic source of truth: each kernel's tests sweep shapes and
dtypes and assert allclose against these functions. They intentionally share
the model's reference attention core (models.attention.attend_reference —
pure jnp, never a kernel, whatever the platform or the pinned backend) so
the kernels are validated against exactly what the model computes.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..models.attention import (PardMaskInfo, TreeAttnInfo,
                                attend_reference, dequantize_kv,
                                gather_pages, latent_attend_reference)
from ..models.ssm import ssd_scan_ref


def _maybe_dequant(k, v, k_scale, v_scale):
    """fp32 semantics for quantized KV: expand against the scales up front
    so the oracle computes on exactly the values the kernel dequantizes."""
    if k_scale is None:
        return k, v
    return dequantize_kv(k, k_scale), dequantize_kv(v, v_scale)


def flash_attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0,
                        scale=None):
    """q: [B,T,Hq,D]; k,v: [B,S,Hkv,D] (GQA: Hq % Hkv == 0)."""
    b, t = q.shape[:2]
    s = k.shape[1]
    q_pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return attend_reference(q, k, v, q_pos, kv_pos, s, causal=causal,
                            window=window, attn_softcap=softcap, scale=scale)


def decode_attention_ref(q, k, v, kv_len, q_pos, *, k_scale=None,
                         v_scale=None, window=0, softcap=0.0, scale=None):
    """Speculative-verify attention: small q against a long KV cache.

    q: [B,Tq,Hq,D]; k,v: [B,S,Hkv,D]; kv_len: [B]; q_pos: [B,Tq] absolute.
    k_scale/v_scale: optional [B,S,Hkv] dequant scales for quantized k/v.
    """
    k, v = _maybe_dequant(k, v, k_scale, v_scale)
    b = q.shape[0]
    s = k.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return attend_reference(q, k, v, q_pos, kv_pos, kv_len, causal=True,
                            window=window, attn_softcap=softcap, scale=scale)


def decode_attention_paged_ref(q, k_pages, v_pages, block_tables, kv_len,
                               q_pos, *, k_scale=None, v_scale=None,
                               window=0, softcap=0.0, scale=None):
    """Paged-pool oracle: gather each row's blocks into a contiguous view
    (models.attention.gather_pages) and defer to the contiguous reference.

    q: [B,Tq,Hq,D]; k_pages, v_pages: [NB, block, Hkv, D];
    block_tables: [B, MBS]; kv_len: [B]; q_pos: [B,Tq] absolute.
    k_scale/v_scale: optional [NB, block, Hkv] per-slot dequant scales.
    """
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    if k_scale is not None:
        k_scale = gather_pages(k_scale, block_tables)
        v_scale = gather_pages(v_scale, block_tables)
    return decode_attention_ref(q, k, v, kv_len, q_pos, k_scale=k_scale,
                                v_scale=v_scale, window=window,
                                softcap=softcap, scale=scale)


def paged_latent_attention_ref(q, pages, block_tables, kv_len, q_pos, *,
                               rank, scale):
    """Absorbed-MLA oracle: q [B,Tq,H,W] against a latent pool [NB, block,
    W] (keys the whole rows, values their first ``rank`` channels), each
    row's pages gathered into a contiguous view, in float32."""
    return latent_attend_reference(q, pages, block_tables, kv_len, q_pos,
                                   rank=rank, scale=scale)


def tree_attention_ref(q, k, v, kv_len, q_pos, win_start, anc, *,
                       win_len=None, k_scale=None, v_scale=None, window=0,
                       softcap=0.0, scale=None):
    """Tree-verification attention: the packed candidate tree window against
    a long cache (DESIGN.md §6). Masking comes from models.attention's
    TreeAttnInfo (packed ancestor bitmask inside the window, plain context
    visibility before it) so the kernel validates against exactly what the
    model computes.

    q: [B,Tq,Hq,D]; k,v: [B,S,Hkv,D]; kv_len: [B]; q_pos: [B,Tq] logical
    positions; win_start: [B] cache index of window slot 0; anc: [B,Tq]
    uint32 ancestor bitmasks; win_len: optional [B] per-row count of
    meaningful window slots (per-request tree templates, DESIGN.md §7);
    k_scale/v_scale: optional [B,S,Hkv] dequant scales for quantized k/v.
    """
    k, v = _maybe_dequant(k, v, k_scale, v_scale)
    b = q.shape[0]
    s = k.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    info = TreeAttnInfo(jnp.asarray(win_start), jnp.asarray(anc),
                        None if win_len is None else jnp.asarray(win_len))
    if win_len is not None:
        # the kernels clamp each row's sweep to win_start + win_len; the
        # oracle realises the same bound through kv_len so padded window
        # slots are invisible on both paths
        kv_len = jnp.minimum(jnp.asarray(kv_len),
                             jnp.asarray(win_start) + jnp.asarray(win_len))
    return attend_reference(q, k, v, q_pos, kv_pos, kv_len, causal=True,
                            window=window, attn_softcap=softcap, scale=scale,
                            tree_info=info)


def tree_attention_paged_ref(q, k_pages, v_pages, block_tables, kv_len,
                             q_pos, win_start, anc, *, win_len=None,
                             k_scale=None, v_scale=None, window=0,
                             softcap=0.0, scale=None):
    """Paged-pool tree-verification oracle: gather each row's blocks into a
    contiguous view and defer to the contiguous reference."""
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    if k_scale is not None:
        k_scale = gather_pages(k_scale, block_tables)
        v_scale = gather_pages(v_scale, block_tables)
    return tree_attention_ref(q, k, v, kv_len, q_pos, win_start, anc,
                              win_len=win_len, k_scale=k_scale,
                              v_scale=v_scale, window=window,
                              softcap=softcap, scale=scale)


def pard_attention_ref(q, k, v, segment, base, *, scale=None, softcap=0.0):
    """PARD-COD training attention; mask from (segment, base) metadata.

    q,k,v: [B,T,H*,D]; segment, base: [B,T] int32 (segment 0 = padding).
    """
    b, t = q.shape[:2]
    pos = jnp.zeros((b, t), jnp.int32)
    info = PardMaskInfo(jnp.asarray(segment), jnp.asarray(base))
    return attend_reference(q, k, v, pos, pos, t, causal=False,
                            attn_softcap=softcap, scale=scale, mask_info=info)


def ssd_ref(x, dt, A, B, C, init_state=None):
    """Token-by-token SSD oracle. x: [b,t,h,p]; dt: [b,t,h] (post-softplus);
    A: [h] (negative); B,C: [b,t,n]. Returns (y, final_state)."""
    return ssd_scan_ref(x, dt, A, B, C, init_state=init_state)
