"""Causal GQA flash attention — Pallas TPU kernel.

Grid: (batch, q_head, num_q_blocks, num_kv_blocks), kv innermost. The
output block is revisited across the kv dimension; running max / sum /
accumulator live in VMEM scratch (the standard TPU flash-attention
structure). Supports sliding-window masking and gemma2-style attention
logit softcapping.

TPU adaptation notes (vs the CUDA flash-attention the paper's frameworks
use): q/k/v run head-major (``[B, H, T, D]``, transposed here) so every
block ``(1, 1, rows, D)`` spans the array's two minor dims — the TPU tiling
rule for a head dim of 64; q blocks of up to 128 rows, kv blocks of 128;
masks are computed from block-relative iotas (no [T,T] mask tensor touches
HBM); fully-masked (q,kv) block pairs are skipped with pl.when on block
indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, scale, causal,
            window, softcap, block_q, block_k, seq_len):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip: causal (kv entirely after q) or window (kv entirely
    # before the window of the newest query in the block)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window) if causal else run

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len                         # padded kv tail
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
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
        denom = l_s[...]
        denom = jnp.where(denom == 0.0, 1.0, denom)   # fully-masked rows -> 0
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, block_q=128, block_k=128, interpret=False):
    """q: [B, T, Hq, D]; k, v: [B, S, Hkv, D]. T and S must be multiples of
    the block sizes (ops.py pads)."""
    b, t, hq, d = q.shape
    s_len = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    grid = (b, hq, pl.cdiv(t, block_q), pl.cdiv(s_len, block_k))

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_len=s_len)

    def q_map(bi, h, qi, ki):
        return (bi, h, qi, 0)

    def kv_map(bi, h, qi, ki):
        return (bi, h // g, ki, 0)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, block_q, d), q_map),
                  pl.BlockSpec((1, 1, block_k, d), kv_map),
                  pl.BlockSpec((1, 1, block_k, d), kv_map)],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
