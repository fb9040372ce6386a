"""Public jit'd wrappers around the Pallas kernels.

Handle padding to TPU-aligned block shapes, GQA head grouping, dtype
plumbing, and interpret-mode dispatch: on a TPU the kernels always compile
natively; elsewhere they run in the Pallas interpreter (so they validate on
a CPU-only host) unless the caller asks for a native compile, as the
compile rehearsal for a described TPU does.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import pard_attention as _pard
from . import ssd as _ssd
from . import tree_attention as _tree


def _interpret(flag):
    if jax.default_backend() == "tpu":
        return False
    return True if flag is None else flag


# The decode / tree wrappers below are NOT jitted: they read the activation
# mesh at trace time (``_over_kv_heads``), and a jit cache keyed on shapes
# alone would hand a trace made under one mesh to a call under another.
# They are traced inline into the serving steps that call them.

# --------------------------------------------------------------------------
# Activation sharding hints (sharded serving, DESIGN.md §11 / §13)
#
# The serving executor traces its fused steps under ``activation_mesh`` so
# the forward can pin GSPMD's layout choices at the places they would
# otherwise drift. Two rulesets share the same seams:
#
# "exact" (default): a model-sharded activation feeding a contraction
# (attention heads into wo, mlp hidden into the down-projection,
# vocab-sharded logits into softmax/argmax) lets the partitioner pick
# partial-sum reduction, whose accumulation order differs from the
# single-device dot. ``partial_activation`` behaves as ``gather_activation``
# — force the all-gather FIRST, so every contraction runs full-operand on
# every device and the tokens match across mesh shapes bitwise.
#
# "throughput": ``partial_activation`` instead KEEPS the activation
# model-sharded on its contraction axis between the column-parallel
# up-projection and the row-parallel down-projection
# (THROUGHPUT_PARAM_RULES). Each device contracts its local shard; the
# post-contraction ``gather_activation`` constrains the partial product to
# replicated, which GSPMD realizes as exactly ONE psum (all-reduce) per
# attention block / MLP instead of per-contraction full-activation
# all-gathers. Tokens then match tp1 only to tolerance (accumulation
# order), never bitwise.
#
# With no mesh set (training, the uniform generate_* paths, tier-1 tests)
# both helpers are identity.
# --------------------------------------------------------------------------

_ACTIVATION_MESH = None
_ACTIVATION_RULESET = "exact"


@contextlib.contextmanager
def activation_mesh(mesh, ruleset="exact"):
    """Trace-time context: the mesh the activation hints constrain onto
    (None = the hints are no-ops) and the serving ruleset steering
    ``partial_activation``. Set around jit TRACING — the hints bake into
    the compiled computation, so the context only needs to wrap the call
    sites that may trigger a (re)trace."""
    global _ACTIVATION_MESH, _ACTIVATION_RULESET
    prev = (_ACTIVATION_MESH, _ACTIVATION_RULESET)
    _ACTIVATION_MESH, _ACTIVATION_RULESET = mesh, ruleset
    try:
        yield
    finally:
        _ACTIVATION_MESH, _ACTIVATION_RULESET = prev


def gather_activation(x):
    """Constrain ``x`` to be fully replicated before a contraction /
    normalization / sampling consumes it. Identity when no activation mesh
    is set. Under the throughput ruleset this is the POST-contraction seam:
    constraining the locally-contracted partial product to replicated is
    what makes GSPMD emit the block's single psum."""
    if _ACTIVATION_MESH is None or x is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_ACTIVATION_MESH,
                                      jax.sharding.PartitionSpec()))


def partial_activation(x, axis=-1):
    """PRE-combine seam between the column- and row-parallel halves.

    Exact ruleset: alias of ``gather_activation`` (full-operand
    contraction, bitwise identity). Throughput ruleset: keep ``x``
    model-sharded on ``axis`` — ``rowparallel_einsum`` applies it to the
    canonical chunk axis of its partial products so the f32 combine over
    that axis lowers to the block's single psum; falls back to the gather
    when the axis does not divide the model mesh (mirroring the replicate
    fallback in THROUGHPUT_PARAM_RULES). Identity when no mesh is set."""
    if _ACTIVATION_MESH is None or x is None:
        return x
    if _ACTIVATION_RULESET != "throughput":
        return gather_activation(x)
    mesh_shape = dict(zip(_ACTIVATION_MESH.axis_names,
                          _ACTIVATION_MESH.devices.shape))
    model = mesh_shape.get("model", 1)
    if model <= 1 or x.shape[axis] % model:
        return gather_activation(x)
    spec = [None] * x.ndim
    spec[axis if axis >= 0 else x.ndim + axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_ACTIVATION_MESH,
                                      jax.sharding.PartitionSpec(*spec)))


def rowparallel_einsum(eq, x, w, *, x_axis, w_axis):
    """Down-projection contraction at a serving-ruleset seam.

    Exact ruleset (and training / no-mesh, where both hints are identity):
    gather ``x`` to replicated and contract whole — the reduction-free
    bitwise path, graph-identical to the pre-ruleset code.

    Throughput ruleset: Megatron row parallelism with numerics pinned at
    canonical-chunk granularity. The contraction dim (``x_axis`` of ``x``
    / ``w_axis`` of ``w``) is reshaped into a ``ROWPARALLEL_CHUNKS`` (=4)
    chunk axis; ONE einsum contracts per chunk (XLA dots accumulate in f32
    and round to the compute dtype once per chunk → bf16 partials), the
    chunk axis is constrained model-sharded, and the partials combine by a
    single f32-upcast sum rounded once. Mesh-size independence falls out
    structurally:

    - model axis = 4: one bf16 chunk-partial per device; the f32 sum over
      the sharded chunk axis lowers to the block's single psum.
    - model axis = 2: two chunk-partials per device; local f32 partial
      sums + a 2-way f32 psum.
    - model axis = 1 (the reference the benchmark gates compare against):
      the same graph with the sum evaluated locally.

    An f32 sum of four bf16-valued terms is exact in f32 arithmetic
    (8-bit mantissas; associativity cannot matter below a ~2^16 exponent
    spread), so every mesh size rounds the SAME real number to bf16 once —
    bitwise-identical greedy tokens across tp1/tp2/tp4, verified by the
    serve_sharded match-rate gate and tests/test_tp_ruleset.py. (XLA CPU's
    bf16 all-reduce computes exactly this f32-upcast-sum-round-once —
    discovered empirically; its HLO shows the reduction ``promoted`` to
    f32 — so the earlier bf16-psum formulation agreed bitwise too, but
    only as a backend property, not by construction.)

    Contraction dim not divisible by 4: replicate fallback (gather + whole
    contraction), mirroring THROUGHPUT_PARAM_RULES' weight-side fallback,
    at every mesh size.
    """
    if _ACTIVATION_MESH is None or _ACTIVATION_RULESET != "throughput":
        return jnp.einsum(eq, gather_activation(x), w)
    from ..sharding.specs import ROWPARALLEL_CHUNKS
    nc = ROWPARALLEL_CHUNKS
    if x.shape[x_axis] % nc or w.shape[w_axis] % nc:
        return jnp.einsum(eq, gather_activation(x), w)
    ins, out = eq.split("->")
    xs, ws = ins.split(",")
    assert "Z" not in eq, eq  # chunk-axis label must be free
    xs2 = xs[:x_axis] + "Z" + xs[x_axis:]
    ws2 = ws[:w_axis] + "Z" + ws[w_axis:]

    def split(a, axis):
        axis = axis % a.ndim
        sh = a.shape
        return a.reshape(sh[:axis] + (nc, sh[axis] // nc) + sh[axis + 1:])

    partials = jnp.einsum(f"{xs2},{ws2}->Z{out}", split(x, x_axis),
                          split(w, w_axis))
    partials = partial_activation(partials, axis=0)
    return jnp.sum(partials.astype(jnp.float32), axis=0).astype(x.dtype)


def _model_axis_size():
    if _ACTIVATION_MESH is None:
        return 1
    return dict(zip(_ACTIVATION_MESH.axis_names,
                    _ACTIVATION_MESH.devices.shape)).get("model", 1)


def _over_kv_heads(fn, args, head_axes, hkv):
    """Run a kernel on each device's own kv heads under the activation
    mesh. A Pallas call cannot be partitioned by GSPMD, so inside a sharded
    step it runs in a ``shard_map`` over "model": the arguments whose kv-head
    axis is given in ``head_axes`` (q, pools, scales, output) split there —
    exactly how the serving rules place the head-sharded projections and KV
    pools — and everything else (tables, lengths, positions) replicates.
    Attention is independent per kv head, so no collective is needed. A kv
    head count the model axis does not divide replicates the whole call.
    Identity without a mesh. ``None`` entries of ``args`` pass through."""
    model = _model_axis_size()
    if model == 1:
        return fn(*args)
    split = hkv % model == 0

    def spec(ax):
        if ax is None or not split:
            return jax.sharding.PartitionSpec()
        return jax.sharding.PartitionSpec(*([None] * ax), "model")

    live = [i for i, a in enumerate(args) if a is not None]

    def local(*xs):
        full = list(args)
        for i, x in zip(live, xs):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        local, mesh=_ACTIVATION_MESH,
        in_specs=tuple(spec(head_axes[i]) for i in live),
        out_specs=spec(2), check_vma=False)(*(args[i] for i in live))


def _pad_axis(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, block_q=128, block_k=128, interpret=None):
    """Drop-in for ref.flash_attention_ref. Pads T/S/D to block multiples."""
    interpret = _interpret(interpret)
    b, t, hq, d = q.shape
    block_q = min(block_q, max(8, 1 << (t - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (k.shape[1] - 1).bit_length()))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q, _ = _pad_axis(q, 1, block_q)
    k, s_orig = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    # padded kv tail is masked via seq_len; padded q rows are dropped below
    out = _fa.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out[:, :t]


def decode_attention(q, k, v, kv_len, q_pos, *, k_scale=None, v_scale=None,
                     window=0, softcap=0.0, scale=None, block_k=256,
                     interpret=None):
    """Decode/verify attention over per-row K/V ``[B, S, Hkv, D]``. The
    serving forward reaches it only through ``attention.attend`` on a
    row's pool pages gathered into a per-row view (windows wider than the
    paged kernel's); the pool itself goes to ``decode_attention_paged``."""
    interpret = _interpret(interpret)
    b, tq, hq, d = q.shape
    block_k = min(block_k, max(8, 1 << (k.shape[1] - 1).bit_length()))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k, _ = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    if k_scale is not None:
        k_scale, _ = _pad_axis(k_scale, 1, block_k)
        v_scale, _ = _pad_axis(v_scale, 1, block_k)
    kern = functools.partial(_dec.decode_attention, window=window,
                             softcap=softcap, scale=scale, block_k=block_k,
                             interpret=interpret)
    return _over_kv_heads(
        lambda q, k, v, kl, qp, ks, vs: kern(q, k, v, kl, qp, k_scale=ks,
                                             v_scale=vs),
        (q, k, v, kv_len, q_pos, k_scale, v_scale),
        (2, 2, 2, None, None, 2, 2), k.shape[2])


def decode_attention_paged(q, k_pages, v_pages, block_tables, kv_len, q_pos,
                           *, k_scale=None, v_scale=None, window=0,
                           softcap=0.0, scale=None, interpret=None):
    """Paged-pool variant: k/v are [NB, block, Hkv, D] pools indirected by
    ``block_tables`` [B, MBS]. The pool's block size IS the kernel's kv
    block, so no padding is needed — the grid sweeps the table entries.
    k_scale/v_scale: optional [NB, block, Hkv] dequant scales for
    quantized pools."""
    interpret = _interpret(interpret)
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_dec.decode_attention_paged, window=window,
                             softcap=softcap, scale=scale,
                             interpret=interpret)
    return _over_kv_heads(
        lambda q, k, v, bt, kl, qp, ks, vs: kern(q, k, v, bt, kl, qp,
                                                 k_scale=ks, v_scale=vs),
        (q, k_pages, v_pages, block_tables, kv_len, q_pos, k_scale, v_scale),
        (2, 2, 2, None, None, None, 2, 2), k_pages.shape[2])


def paged_latent_attention(q, pages, block_tables, kv_len, q_pos, *, rank,
                           scale, interpret=None):
    """Absorbed MLA against a paged latent pool [NB, block, rank + dr]
    (decode_attention.paged_latent_attention). Its one latent "head" is
    shared by every query head, so under a mesh the call replicates."""
    interpret = _interpret(interpret)
    kern = functools.partial(_dec.paged_latent_attention, rank=rank,
                             scale=scale, interpret=interpret)
    return _over_kv_heads(kern, (q, pages, block_tables, kv_len, q_pos),
                          (None,) * 5, 1)


GMM_ROWS = 128          # row tile of the grouped matmul


def grouped_matmul(lhs, rhs, group_sizes, *, interpret=None):
    """Rows of ``lhs`` [m, k], sorted by group, times their group's matrix
    of ``rhs`` [G, k, n]: group g's ``group_sizes[g]`` rows after the
    groups before it (megablox ``gmm``, a Pallas kernel). Rows past the
    last group are not computed and come back undefined. The kernel
    visits only the row tiles that hold a group's rows, so its time tracks
    the rows routed, not ``m``. Tiles: 128 rows, the whole contraction,
    and 256 or 128 output lanes (the fastest of the tilings tried on a
    v5e at DeepSeek-V2's expert widths, PERF.md). It cannot be
    partitioned, so under an activation mesh it replicates."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    interpret = _interpret(interpret)
    m, k = lhs.shape
    n = rhs.shape[2]
    tn = next((t for t in (256, 128) if n % t == 0), n)
    lhs, _ = _pad_axis(lhs, 0, GMM_ROWS)

    def call(x, w, sizes):
        return gmm(x, w, sizes, preferred_element_type=lhs.dtype,
                   tiling=(GMM_ROWS, k, tn), interpret=interpret)

    out = _over_kv_heads(call, (lhs, rhs, group_sizes.astype(jnp.int32)),
                         (None, None, None), 1)
    return out[:m]


def tree_attention(q, k, v, kv_len, q_pos, win_start, anc, *, win_len=None,
                   k_scale=None, v_scale=None, window=0, softcap=0.0,
                   scale=None, block_k=256, interpret=None):
    """Tree-verification attention over per-row K/V ``[B, S, Hkv, D]`` —
    in serving, a row's pool pages gathered into a per-row view (MLA tree
    verification's gather path); the pool itself goes to
    ``tree_attention_paged``. ``anc`` is
    the [B, Tq] uint32 packed ancestor bitmask (bit j = window slot j
    visible); ``win_start`` the cache index of window slot 0; ``win_len``
    the optional [B] per-row count of meaningful window slots (per-request
    tree templates — None means all Tq slots); k_scale/v_scale: optional
    [B, S, Hkv] dequant scales for quantized k/v."""
    interpret = _interpret(interpret)
    d = q.shape[-1]
    block_k = min(block_k, max(8, 1 << (k.shape[1] - 1).bit_length()))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k, _ = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    if k_scale is not None:
        k_scale, _ = _pad_axis(k_scale, 1, block_k)
        v_scale, _ = _pad_axis(v_scale, 1, block_k)
    kern = functools.partial(_tree.tree_attention, window=window,
                             softcap=softcap, scale=scale, block_k=block_k,
                             interpret=interpret)
    return _over_kv_heads(
        lambda q, k, v, kl, qp, ws, an, wl, ks, vs: kern(
            q, k, v, kl, qp, ws, an, win_len=wl, k_scale=ks, v_scale=vs),
        (q, k, v, kv_len, q_pos, win_start, anc, win_len, k_scale, v_scale),
        (2, 2, 2, None, None, None, None, None, 2, 2), k.shape[2])


def tree_attention_paged(q, k_pages, v_pages, block_tables, kv_len, q_pos,
                         win_start, anc, *, win_len=None, k_scale=None,
                         v_scale=None, window=0, softcap=0.0, scale=None,
                         interpret=None):
    """Paged-pool tree verification: k/v are [NB, block, Hkv, D] pools
    indirected by ``block_tables`` [B, MBS]; the pool's block size IS the
    kernel's kv block (no padding), exactly like decode_attention_paged.
    ``win_len``: optional [B] per-row meaningful window slots;
    k_scale/v_scale: optional [NB, block, Hkv] dequant scales for
    quantized pools."""
    interpret = _interpret(interpret)
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_tree.tree_attention_paged, window=window,
                             softcap=softcap, scale=scale,
                             interpret=interpret)
    return _over_kv_heads(
        lambda q, k, v, bt, kl, qp, ws, an, wl, ks, vs: kern(
            q, k, v, bt, kl, qp, ws, an, win_len=wl, k_scale=ks, v_scale=vs),
        (q, k_pages, v_pages, block_tables, kv_len, q_pos, win_start, anc,
         win_len, k_scale, v_scale),
        (2, 2, 2, None, None, None, None, None, None, 2, 2),
        k_pages.shape[2])


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "block_q", "block_k", "interpret"))
def pard_attention(q, k, v, segment, base, *, scale=None, softcap=0.0,
                   block_q=128, block_k=128, interpret=None):
    """GQA is handled by repeating KV heads (draft models are small; the
    repeat is cheap relative to the mask-aware attention itself)."""
    interpret = _interpret(interpret)
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    block_q = min(block_q, max(8, 1 << (t - 1).bit_length()))
    block_k = min(block_k, block_q)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q, _ = _pad_axis(q, 1, block_q)
    k, _ = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    seg, _ = _pad_axis(segment.astype(jnp.int32), 1, block_q)  # pad seg=0
    bas, _ = _pad_axis(base.astype(jnp.int32), 1, block_q)
    out = _pard.pard_attention(q, k, v, seg, bas, scale=scale,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x, dt, A, B, C, init_state=None, *, chunk=128,
                interpret=None):
    interpret = _interpret(interpret)
    b, t, h, p = x.shape
    chunk = min(chunk, max(8, 1 << (t - 1).bit_length()))
    x, t_orig = _pad_axis(x, 1, chunk)
    dt, _ = _pad_axis(dt, 1, chunk)      # padded dt=0 -> exp(0)=1, x=0: no-op
    B, _ = _pad_axis(B, 1, chunk)
    C, _ = _pad_axis(C, 1, chunk)
    y, sf = _ssd.ssd_chunked_kernel(x, dt, A, B, C, init_state, chunk=chunk,
                                    interpret=interpret)
    return y[:, :t_orig], sf
