"""PARD-COD training attention — Pallas TPU kernel.

The paper's Fig. 4/5 attention pattern for packed mask-token training. GPU
implementations materialise a sparse/compacted attention mask; on TPU we
compute the mask *functionally inside the kernel* from two int32 metadata
vectors per token — (segment, base) — so the packed COD batch runs as one
dense-blocked flash attention and no O(T^2) mask ever exists in HBM.

Allowed q(s_q, b_q) -> k(s_k, b_k):
    s_k == 1           and b_k <  b_q     (real context)
    1 < s_k < s_q      and b_k == b_q     (earlier masks of the same chain)
    s_k == s_q         and b_k == b_q     (self)
plus segment > 0 on both sides (0 = padding).

Grid: (batch, head, num_q_blocks, num_kv_blocks); q/k/v run head-major
(``[B, H, T, D]``) so each block spans the two minor dims (the TPU tiling
rule), and the metadata streams beside the K/V tiles as a ``[bq, 1]``
column for the queries and a ``[1, bk]`` row for the keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qseg_ref, qbase_ref, kseg_ref, kbase_ref, q_ref, k_ref, v_ref,
            o_ref, m_s, l_s, acc_s, *, scale, softcap):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qs = qseg_ref[0]                                   # [bq, 1]
    qb = qbase_ref[0]
    ks = kseg_ref[0]                                   # [1, bk]
    kb = kbase_ref[0]
    real_ctx = (ks == 1) & (kb < qb)
    chain = (ks > 1) & (ks < qs) & (kb == qb)
    self_tok = (ks == qs) & (kb == qb)
    mask = (qs > 0) & (ks > 0) & (real_ctx | chain | self_tok)

    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _final():
        denom = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


def pard_attention(q, k, v, segment, base, *, scale=None, softcap=0.0,
                   block_q=128, block_k=128, interpret=False):
    """q,k,v: [B, T, H, D]; segment, base: [B, T] int32 (segment 0 = pad).
    Self-attention over the packed COD layout (Hq == Hkv here; the draft
    models PARD adapts are small GQA/MHA models — ops.py pre-repeats KV if
    grouped)."""
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    grid = (b, h, pl.cdiv(t, block_q), pl.cdiv(t, block_k))

    kern = functools.partial(_kernel, scale=scale, softcap=softcap)
    seg = segment.astype(jnp.int32)
    bas = base.astype(jnp.int32)

    def q_map(bi, hh, qi, ki):
        return (bi, hh, qi, 0)

    def kv_map(bi, hh, qi, ki):
        return (bi, hh, ki, 0)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, hh, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bi, hh, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hh, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hh, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="pard_attention",
    )(seg[:, :, None], bas[:, :, None], seg[:, None, :], bas[:, None, :],
      *(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
