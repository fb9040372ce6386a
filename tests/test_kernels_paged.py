"""Paged GQA decode and tree kernels against the ref.py oracles
(interpret mode on the CPU): shuffled tables, the garbage block, the page
sweep over every edge fill, and pools stacked over layers."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

from kernel_inputs import paged_setup, rand, seen_queries, sweep_fills


@pytest.mark.parametrize("b,tq,hq,hkv,d,bs,mbs", [
    (2, 9, 4, 2, 64, 32, 4),     # PARD verify window (K+1 = 9)
    (3, 1, 4, 4, 32, 16, 5),     # plain AR decode
    (1, 8, 8, 2, 32, 64, 3),     # 2K draft window
    (2, 9, 12, 2, 128, 16, 37),  # swept, groups of 16 over 37 entries
    (3, 16, 4, 2, 128, 64, 5),   # swept, groups of 4 over 5 entries
])
def test_decode_attention_paged(b, tq, hq, hkv, d, bs, mbs):
    q = rand(b, tq, hq, d, k=4)
    kv_len = jnp.asarray([bs * mbs // 2 + 3 * i + tq for i in range(b)],
                         jnp.int32)
    k_pages, v_pages, tables, k_cont, v_cont = paged_setup(b, hkv, d, bs,
                                                           mbs)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                     q_pos)
    want = ref.decode_attention_paged_ref(q, k_pages, v_pages, tables,
                                          kv_len, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # cross-layout: the contiguous kernel on the gathered view must agree
    cont = ops.decode_attention(q, k_cont, v_cont, kv_len, q_pos, block_k=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(cont), atol=2e-5)


def test_decode_attention_paged_window_softcap():
    b, tq, h, d, bs, mbs = 2, 3, 4, 32, 16, 6
    q = rand(b, tq, h, d, k=7)
    kv_len = jnp.asarray([77, 60], jnp.int32)
    k_pages, v_pages, tables, _, _ = paged_setup(b, h, d, bs, mbs, key=30)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                     q_pos, window=24, softcap=30.0)
    want = ref.decode_attention_paged_ref(q, k_pages, v_pages, tables,
                                          kv_len, q_pos, window=24,
                                          softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_attention_paged_ignores_garbage_block():
    """Unallocated table entries point at block 0; its contents must never
    leak into the output (kv_len masks them)."""
    b, tq, h, d, bs, mbs = 1, 2, 2, 16, 8, 4
    q = rand(b, tq, h, d, k=40)
    k_pages = rand(6, bs, h, d, k=41)
    v_pages = rand(6, bs, h, d, k=42)
    tables = jnp.asarray([[3, 5, 0, 0]], jnp.int32)     # 2 real blocks
    kv_len = jnp.asarray([14], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out1 = ops.decode_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                      q_pos)
    poisoned_k = k_pages.at[0].set(1e4)
    poisoned_v = v_pages.at[0].set(-1e4)
    out2 = ops.decode_attention_paged(q, poisoned_k, poisoned_v, tables,
                                      kv_len, q_pos)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=0)


@pytest.mark.parametrize("kv", ["f32", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("tables", ["shuffled", "shared"])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (40, 30.0)])
@pytest.mark.parametrize("reverse", [False, True])
def test_decode_attention_paged_sweeps_mixed_fills(kv, tables, window,
                                                    softcap, reverse):
    """Rows of every edge fill in one call, through a table of 11 entries
    that P does not divide, in shuffled or shared (every row the same
    pages) order, with and without window and softcap, the empty row first
    or last. The served 64-slot pages of f32 and bf16 pools (2 kv heads of
    128) are swept in groups (4 and 8 pages); the 8-bit pools and their
    scales take one page a grid step. Table entries past a row's fill point
    at the garbage block, filled with NaN: a page copied past ``kv_len``
    would leak it."""
    from repro.kernels import decode_attention
    from repro.models.attention import quantize_kv
    tq, hq, hkv, d, bs, mbs = 9, 12, 2, 128, 64, 11
    dtype = jnp.float32 if kv == "f32" else jnp.bfloat16
    nb = 1 + mbs
    k_pages = rand(nb, bs, hkv, d, k=50, dtype=dtype)
    v_pages = rand(nb, bs, hkv, d, k=51, dtype=dtype)
    group = decode_attention.sweep_group([k_pages, v_pages], mbs)
    assert group == (4 if kv == "f32" else 8)
    fills = sweep_fills(bs, group, mbs)[::-1 if reverse else 1]
    b = len(fills)
    rng = np.random.default_rng(5)
    perm = [rng.permutation(np.arange(1, nb)) for _ in range(b)]
    tab = np.stack([perm[0] if tables == "shared" else p for p in perm])
    kv_len = jnp.asarray(fills, jnp.int32)
    tab[np.arange(mbs)[None, :] * bs >= np.asarray(fills)[:, None]] = 0
    tab = jnp.asarray(tab, jnp.int32)
    ks = vs = None
    if kv in ("int8", "fp8"):
        qd = jnp.int8 if kv == "int8" else jnp.float8_e4m3fn
        k_pages, ks = quantize_kv(k_pages, qd)
        v_pages, vs = quantize_kv(v_pages, qd)
        assert decode_attention.sweep_group(
            [k_pages, v_pages, ks, vs], mbs) == 0
    q = rand(b, tq, hq, d, k=52, dtype=dtype)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    args = dict(k_scale=ks, v_scale=vs, window=window, softcap=softcap)
    want = ref.decode_attention_paged_ref(q, k_pages, v_pages, tab, kv_len,
                                          q_pos, **args)
    poison = jnp.asarray(jnp.nan, k_pages.dtype) if kv != "int8" else 127
    out = ops.decode_attention_paged(q, k_pages.at[0].set(poison),
                                     v_pages.at[0].set(poison), tab, kv_len,
                                     q_pos, **args)
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    seen = seen_queries(q_pos, kv_len)
    tol = 2e-5 if kv == "f32" else 2e-2
    np.testing.assert_allclose(out[seen], want[seen], atol=tol, rtol=tol)
    assert not out[~seen].any()


@pytest.mark.parametrize("layer", [0, 3])
def test_decode_attention_sweep_reads_stacked_pool_at_layer(layer):
    """The swept kernel (bf16, 2 kv heads of 128) reads the layer loop's
    stacked pool viewed as one through offset tables: the oracle on
    pool[layer]."""
    from repro.models.attention import _paged_attend, set_attention_backend
    n_layers, b, tq, hq, hkv, d, bs, mbs = 4, 3, 16, 4, 2, 128, 64, 5
    nb = 1 + b * mbs
    k_pages = rand(n_layers, nb, bs, hkv, d, k=63, dtype=jnp.bfloat16)
    v_pages = rand(n_layers, nb, bs, hkv, d, k=64, dtype=jnp.bfloat16)
    perm = np.random.default_rng(layer).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mbs), jnp.int32)
    q = rand(b, tq, hq, d, k=65, dtype=jnp.bfloat16)
    kv_len = jnp.asarray([bs * mbs, 4 * bs + 1, tq], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    set_attention_backend("pallas")
    try:
        out = jax.jit(lambda i: _paged_attend(
            q, k_pages, v_pages, tables, q_pos, kv_len, layer=i))(
                jnp.int32(layer))
    finally:
        set_attention_backend(None)
    want = ref.decode_attention_paged_ref(q, k_pages[layer], v_pages[layer],
                                          tables, kv_len, q_pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("kernel", ["decode", "tree"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_paged_kernels_read_stacked_pool_at_layer(kernel, kv, layer):
    """The layer loop hands the paged kernels a pool stacked over layers
    ``[L, NB, bs, Hkv, D]`` and a traced layer index (``_paged_attend``):
    a bf16 pool viewed as ``[L * NB, ...]`` through offset block tables, an
    8-bit pool and its scales sliced at the layer. Either must equal the
    oracle on the slice ``pool[layer]``."""
    from repro.models.attention import (TreeAttnInfo, _paged_attend,
                                        quantize_kv, set_attention_backend)
    n_layers, b, tq, hq, hkv, d, bs, mbs = 5, 2, 9, 4, 2, 64, 32, 4
    nb = 1 + b * mbs
    k_pages = rand(n_layers, nb, bs, hkv, d, k=60, dtype=jnp.bfloat16)
    v_pages = rand(n_layers, nb, bs, hkv, d, k=61, dtype=jnp.bfloat16)
    ks = vs = None
    if kv == "int8":
        k_pages, ks = quantize_kv(k_pages, jnp.int8)
        v_pages, vs = quantize_kv(v_pages, jnp.int8)
    perm = np.random.default_rng(layer).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mbs), jnp.int32)
    q = rand(b, tq, hq, d, k=62)
    kv_len = jnp.asarray([bs * mbs - 7, bs * 2 + tq], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    win_start = kv_len - tq
    anc = jnp.broadcast_to(
        jnp.asarray([(1 << (s + 1)) - 1 for s in range(tq)], jnp.uint32),
        (b, tq))
    tree = TreeAttnInfo(win_start, anc) if kernel == "tree" else None

    def at(x, i):
        return None if x is None else x[i]

    set_attention_backend("pallas")
    try:
        out = jax.jit(lambda i: _paged_attend(
            q, k_pages, v_pages, tables, q_pos, kv_len, tree_info=tree,
            k_scale=ks, v_scale=vs, layer=i))(jnp.int32(layer))
    finally:
        set_attention_backend(None)
    if kernel == "decode":
        want = ref.decode_attention_paged_ref(
            q, k_pages[layer], v_pages[layer], tables, kv_len, q_pos,
            k_scale=at(ks, layer), v_scale=at(vs, layer))
    else:
        want = ref.tree_attention_paged_ref(
            q, k_pages[layer], v_pages[layer], tables, kv_len, q_pos,
            win_start, anc, k_scale=at(ks, layer), v_scale=at(vs, layer))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _paged_call(kernel, q, k_pages, v_pages, tables, kv_len, q_pos, **kw):
    """The decode or tree kernel (a chain: each window slot sees the ones
    before it) over the same pools and queries."""
    if kernel == "tree":
        tq = q.shape[1]
        anc = jnp.broadcast_to(jnp.asarray(
            [(1 << (s + 1)) - 1 for s in range(tq)], jnp.uint32),
            (q.shape[0], tq))
        return ops.tree_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                        q_pos, kv_len - tq, anc, **kw)
    return ops.decode_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                      q_pos, **kw)


@pytest.mark.parametrize("kernel,tq,hq,d,bs,mbs", [
    ("decode", 9, 12, 128, 64, 5),    # swept, the cell's verify window
    ("decode", 16, 12, 128, 64, 5),   # swept, the cell's draft window
    ("decode", 9, 4, 64, 32, 4),      # one page a grid step (head dim 64)
    ("tree", 9, 12, 128, 64, 3),      # tree kernel, one page a grid step
])
def test_bf16_operands_match_float32_path(kernel, tq, hq, d, bs, mbs):
    """bf16 queries against bf16 pools are scored on bf16 MXU operands
    (QK in one pass, PV on p's bf16 high part and remainder). The same
    values with the pools upcast to float32 take the float32 path. The two
    compute the same products up to summation order and p's mantissa past
    16 bits, so the bf16 outputs agree within one bf16 ulp: a relative
    tolerance of bfloat16's eps (the ulp of a value is at most eps times
    it), and an absolute one of eps**2 for outputs near zero, where the
    float32 paths' difference (about 2**-17 of the largest value) is all
    that is left."""
    b, hkv = 3, 2
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    k_pages, v_pages, tables, _, _ = paged_setup(b, hkv, d, bs, mbs, key=70)
    k_pages, v_pages = (x.astype(jnp.bfloat16) for x in (k_pages, v_pages))
    q = rand(b, tq, hq, d, k=73, dtype=jnp.bfloat16)
    kv_len = jnp.asarray([bs * mbs, bs * mbs // 2 + 5, tq], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = _paged_call(kernel, q, k_pages, v_pages, tables, kv_len, q_pos)
    want = _paged_call(kernel, q, k_pages.astype(jnp.float32),
                       v_pages.astype(jnp.float32), tables, kv_len, q_pos)
    assert out.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=eps,
                               atol=eps ** 2)


def _dot_operand_dtypes(jaxpr):
    """The operand dtypes of every ``dot_general`` in ``jaxpr`` and the
    jaxprs nested in its equations (loops, conditionals, kernels)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(str(v.aval.dtype) for v in eqn.invars))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else [p]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _dot_operand_dtypes(inner)
    return found


@pytest.mark.parametrize("kernel", ["decode", "tree"])
@pytest.mark.parametrize("pool,query,operands", [
    ("bf16", "bf16", "bfloat16"),
    ("f32", "bf16", "float32"),
    ("bf16", "f32", "float32"),
    ("int8", "bf16", "float32"),
    ("fp8", "bf16", "float32"),
])
def test_kernel_matmul_operands_follow_pool(kernel, pool, query, operands):
    """Inside the pallas_call, every QK and PV matmul takes bf16 operands
    for bf16 queries against a bf16 pool, and float32 operands for any
    float32 input and for quantized pools (their dequant scales are
    float32): a quantized pool never moves onto bf16 operands."""
    from repro.models.attention import quantize_kv
    b, tq, hq, hkv, d, bs, mbs = 2, 9, 12, 2, 128, 64, 3
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}
    k_pages, v_pages, tables, _, _ = paged_setup(b, hkv, d, bs, mbs, key=80)
    kw = {}
    if pool in ("int8", "fp8"):
        qd = jnp.int8 if pool == "int8" else jnp.float8_e4m3fn
        k_pages, kw["k_scale"] = quantize_kv(k_pages, qd)
        v_pages, kw["v_scale"] = quantize_kv(v_pages, qd)
    else:
        k_pages, v_pages = (x.astype(dt[pool]) for x in (k_pages, v_pages))
    q = rand(b, tq, hq, d, k=81, dtype=dt[query])
    kv_len = jnp.asarray([bs * mbs, tq], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    jaxpr = jax.make_jaxpr(functools.partial(_paged_call, kernel, **kw))(
        q, k_pages, v_pages, tables, kv_len, q_pos).jaxpr
    kernels = [e.params["jaxpr"] for e in jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    dots = _dot_operand_dtypes(kernels[0])
    assert dots and set(dots) == {(operands, operands)}, dots
