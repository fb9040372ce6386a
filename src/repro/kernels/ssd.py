"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid: (batch, head, num_chunks), chunks innermost; the running state
S [P, N] lives in VMEM scratch and is carried across chunk iterations
(sequential dependence is exactly the flash-attention revisiting pattern,
with the state playing the accumulator role).

Per chunk (length L):
  cum_t   = cumsum(dt_t * A)                         (log-decay prefix)
  y_intra = ((C B^T) ∘ exp(cum_i - cum_j) ∘ causal) @ (dt x)
  y_state = (C @ S_in) * exp(cum)
  S_out   = S_in * exp(cum_L) + sum_j exp(cum_L - cum_j) dt_j x_j B_j^T

The intra-chunk term is two MXU matmuls of shape [L,N]x[N,L] and [L,L]x[L,P]
— chunk length L is chosen 128/256 so both hit the systolic array at full
tile occupancy; dt/A gating is VPU elementwise work on an [L, 1] column.
x/y run head-major (``[b, h, t, p]``) so their blocks span the two minor
dims (the TPU tiling rule), dt arrives as that column, and the per-head
A sits whole in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, s0_ref, y_ref, sf_ref, s_s,
            *, chunk):
    hh, ci = pl.program_id(1), pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_s[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)               # [L, P]
    dt = dt_ref[0, 0].astype(jnp.float32)             # [L, 1]
    B = b_ref[0].astype(jnp.float32)                  # [L, N]
    C = c_ref[0].astype(jnp.float32)                  # [L, N]

    dtA = dt * a_ref[hh]                               # [L, 1]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sums as masked reductions (VPU/XLU only), in row
    # form cum[j] and column form cum[i]; the diagonal pick transposes dtA
    dtA_ij = jnp.broadcast_to(dtA, (chunk, chunk))    # [i, j] = dtA[i]
    dtA_row = jnp.sum(jnp.where(i == j, dtA_ij, 0.0), axis=0,
                      keepdims=True)                   # [1, L]
    cum_row = jnp.sum(jnp.where(i <= j, dtA_ij, 0.0), axis=0,
                      keepdims=True)                   # [1, L]
    cum_col = jnp.sum(jnp.where(i >= j, jnp.broadcast_to(
        dtA_row, (chunk, chunk)), 0.0), axis=1, keepdims=True)   # [L, 1]
    total = jnp.sum(dtA, axis=0, keepdims=True)       # [1, 1]
    w = jnp.where(i >= j, jnp.exp(cum_col - cum_row), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [L, L]
    xdt = x * dt                                       # [L, P]
    y_intra = jnp.dot(w * cb, xdt, preferred_element_type=jnp.float32)

    S = s_s[...]                                       # [P, N]
    y_state = jax.lax.dot_general(C, S, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32
                                  ) * jnp.exp(cum_col)  # [L, P]
    y_ref[0, 0] = (y_intra + y_state).astype(y_ref.dtype)

    decayed = xdt * jnp.exp(total - cum_col)           # [L, P]
    S_new = S * jnp.exp(total) + jax.lax.dot_general(
        decayed, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [P, N]
    s_s[...] = S_new

    @pl.when(ci == nc - 1)
    def _final():
        sf_ref[0, 0] = S_new.astype(sf_ref.dtype)


def ssd_chunked_kernel(x, dt, A, B, C, init_state=None, *, chunk=128,
                       interpret=False):
    """x: [b, t, h, p]; dt: [b, t, h] (post-softplus); A: [h] (negative);
    B, C: [b, t, n]; init_state: [b, h, p, n] or None.
    Returns (y [b,t,h,p], final_state [b,h,p,n]). t must be a multiple of
    ``chunk`` (ops.py pads)."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    assert t % chunk == 0, "pad t to a chunk multiple in ops.py"
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    nc = t // chunk

    def head_map(bi, hh, ci):
        return (bi, hh, ci, 0)

    def state_map(bi, hh, ci):
        return (bi, hh, 0, 0)

    def bc_map(bi, hh, ci):                # B/C are shared across heads
        return (bi, ci, 0)

    kern = functools.partial(_kernel, chunk=chunk)
    y, sf = pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # A [h]
            pl.BlockSpec((1, 1, chunk, p), head_map),          # x
            pl.BlockSpec((1, 1, chunk, 1), head_map),          # dt
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, 1, p, n), state_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), head_map),
            pl.BlockSpec((1, 1, p, n), state_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd",
    )(A.astype(jnp.float32), x.transpose(0, 2, 1, 3),
      dt.transpose(0, 2, 1)[..., None], B, C, init_state.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3), sf
