"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
ref.py pure-jnp oracles (interpret=True executes the Pallas kernel bodies on
CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def rand(*shape, k=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32
                             ).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,t,hq,hkv,d", [
    (1, 64, 4, 4, 32), (2, 128, 4, 2, 64), (1, 96, 8, 1, 32), (2, 50, 2, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, t, hq, hkv, d, dtype):
    q = rand(b, t, hq, d, k=1, dtype=dtype)
    k = rand(b, t, hkv, d, k=2, dtype=dtype)
    v = rand(b, t, hkv, d, k=3, dtype=dtype)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0), (0, 30.0),
                                            (32, 50.0)])
def test_flash_attention_variants(window, softcap):
    q, k, v = (rand(2, 64, 4, 32, k=i) for i in (1, 2, 3))
    out = ops.flash_attention(q, k, v, window=window, softcap=softcap,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("b,tq,hq,hkv,d,s", [
    (2, 9, 4, 2, 64, 256), (3, 1, 4, 4, 32, 128), (1, 5, 8, 2, 32, 100),
])
def test_decode_attention(b, tq, hq, hkv, d, s):
    q = rand(b, tq, hq, d, k=4)
    k = rand(b, s, hkv, d, k=5)
    v = rand(b, s, hkv, d, k=6)
    kv_len = jnp.asarray([s // 2 + 3 * i + tq for i in range(b)], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention(q, k, v, kv_len, q_pos, block_k=64)
    want = ref.decode_attention_ref(q, k, v, kv_len, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _paged_setup(b, hkv, d, bs, mbs, key=0):
    """A pool with rows owning interleaved (non-monotone) blocks, plus the
    equivalent contiguous cache for cross-layout parity checks."""
    nb = 1 + b * mbs
    k_pages = rand(nb, bs, hkv, d, k=key + 1)
    v_pages = rand(nb, bs, hkv, d, k=key + 2)
    perm = np.random.default_rng(key).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mbs), jnp.int32)
    k_cont = ref.gather_pages(k_pages, tables)
    v_cont = ref.gather_pages(v_pages, tables)
    return k_pages, v_pages, tables, k_cont, v_cont


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
    k_pages, v_pages, tables, k_cont, v_cont = _paged_setup(b, hkv, d, bs,
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
    k_pages, v_pages, tables, _, _ = _paged_setup(b, h, d, bs, mbs, key=30)
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


def _sweep_fills(bs, group, mbs):
    """One row per edge of the sweep: empty, one slot, a page less one, a
    page, a group less one, a group, a group and one, the whole table."""
    return [0, 1, bs - 1, bs, group * bs - 1, group * bs, group * bs + 1,
            mbs * bs]


def _seen(q_pos, kv_len):
    """[B, Tq] queries that see at least one key (the rest are padding:
    the kernels write zeros there, the oracle an average)."""
    return np.asarray((q_pos >= 0) & (kv_len[:, None] > 0))


@pytest.mark.parametrize("kv", ["f32", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("tables", ["shuffled", "shared"])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (40, 30.0)])
@pytest.mark.parametrize("reverse", [False, True])
def test_decode_attention_paged_sweeps_mixed_fills(kv, tables, window,
                                                    softcap, reverse):
    """Rows of every edge fill in one call, through a table of 37 entries
    that P does not divide, in shuffled or shared (every row the same
    pages) order, with and without window and softcap, the empty row first
    or last. The f32 and bf16 pools (2 kv heads of 128) are swept in
    groups (16 and 32 pages); the 8-bit pools and their scales take one
    page a grid step. Table entries past a row's fill point at the garbage
    block, filled with NaN: a page copied past ``kv_len`` would leak it."""
    from repro.kernels import decode_attention
    from repro.models.attention import quantize_kv
    tq, hq, hkv, d, bs, mbs = 9, 12, 2, 128, 16, 37
    dtype = jnp.float32 if kv == "f32" else jnp.bfloat16
    nb = 1 + mbs
    k_pages = rand(nb, bs, hkv, d, k=50, dtype=dtype)
    v_pages = rand(nb, bs, hkv, d, k=51, dtype=dtype)
    group = decode_attention.sweep_group([k_pages, v_pages], mbs)
    assert group == (16 if kv == "f32" else 32)
    fills = _sweep_fills(bs, group, mbs)[::-1 if reverse else 1]
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
    seen = _seen(q_pos, kv_len)
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


# latent (absorbed MLA) pages: rank 64 + rope 32 = 96 lanes, padded to 128
LAT_RANK, LAT_ROPE, LAT_LANES = 64, 32, 128


def _latent_setup(b, h, tq, bs, mbs, dtype, k=70, layers=None):
    """Queries [b, tq, h, rank + rope], a pool of 1 + b*mbs pages (stacked
    over ``layers`` when given) whose rows carry zero lanes past rank +
    rope, shuffled tables, and per-row lengths that end mid-page."""
    w = LAT_RANK + LAT_ROPE
    nb = 1 + b * mbs
    lead = () if layers is None else (layers,)
    rows = rand(*lead, nb, bs, w, k=k + 1, dtype=dtype)
    pages = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, LAT_LANES - w)])
    perm = np.random.default_rng(k).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mbs), jnp.int32)
    kv_len = jnp.asarray([bs * mbs - 5 - 3 * i for i in range(b)], jnp.int32)
    q = rand(b, tq, h, w, k=k + 2, dtype=dtype)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    return q, pages, tables, kv_len, q_pos


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,tq,h,bs,mbs", [
    (2, 9, 4, 16, 4),      # PARD verify window (K+1 = 9), last page partial
    (3, 16, 2, 8, 5),      # 2K draft window
    (2, 1, 8, 16, 3),      # plain AR decode
    (3, 9, 4, 64, 37),     # groups of 32 (bf16) or 16 (f32), 37 entries
])
def test_paged_latent_attention(dtype, b, tq, h, bs, mbs):
    q, pages, tables, kv_len, q_pos = _latent_setup(b, h, tq, bs, mbs, dtype)
    scale = 0.11472
    out = ops.paged_latent_attention(q, pages, tables, kv_len, q_pos,
                                     rank=LAT_RANK, scale=scale)
    want = ref.paged_latent_attention_ref(q, pages, tables, kv_len, q_pos,
                                          rank=LAT_RANK, scale=scale)
    assert out.shape == (b, tq, h, LAT_RANK) and out.dtype == dtype
    # bf16: the kernel feeds bf16 probabilities to the PV product and
    # rounds its output to bf16 (2^-9 relative each, on values of order 1);
    # f32: the same arithmetic as the oracle, up to summation order
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=TOL[dtype])


def test_paged_latent_attention_ignores_garbage_block_and_pad_lanes():
    """Block 0 and the pool's pad lanes never reach the output."""
    q, pages, tables, kv_len, q_pos = _latent_setup(2, 4, 9, 16, 4,
                                                    jnp.float32, k=80)
    out1 = ops.paged_latent_attention(q, pages, tables, kv_len, q_pos,
                                      rank=LAT_RANK, scale=0.2)
    poisoned = pages.at[0].set(1e4).at[..., LAT_RANK + LAT_ROPE:].set(-1e4)
    out2 = ops.paged_latent_attention(q, poisoned, tables, kv_len, q_pos,
                                      rank=LAT_RANK, scale=0.2)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("reverse", [False, True])
def test_paged_latent_attention_sweeps_mixed_fills(dtype, reverse):
    """Rows of every edge fill in one call (``_sweep_fills``), 64-slot pages
    of 128 lanes in groups of 16 (f32) or 32 (bf16) over 37 entries. Entries
    past a row's fill point at the garbage block and the pad lanes past
    rank + rope hold NaN: neither may reach the output."""
    from repro.kernels import decode_attention
    tq, h, bs, mbs = 9, 4, 64, 37
    w = LAT_RANK + LAT_ROPE
    nb = 1 + mbs
    pages = jnp.pad(rand(nb, bs, w, k=95, dtype=dtype),
                    [(0, 0), (0, 0), (0, LAT_LANES - w)])
    group = decode_attention.group_pages(
        decode_attention.page_bytes([pages]), mbs)
    assert group == (32 if dtype == jnp.bfloat16 else 16)
    fills = _sweep_fills(bs, group, mbs)[::-1 if reverse else 1]
    b = len(fills)
    rng = np.random.default_rng(6)
    tab = np.stack([rng.permutation(np.arange(1, nb)) for _ in range(b)])
    tab[np.arange(mbs)[None, :] * bs >= np.asarray(fills)[:, None]] = 0
    tab = jnp.asarray(tab, jnp.int32)
    kv_len = jnp.asarray(fills, jnp.int32)
    q = rand(b, tq, h, w, k=96, dtype=dtype)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    want = ref.paged_latent_attention_ref(q, pages, tab, kv_len, q_pos,
                                          rank=LAT_RANK, scale=0.2)
    poisoned = pages.at[0].set(jnp.nan).at[..., w:].set(jnp.nan)
    out = ops.paged_latent_attention(q, poisoned, tab, kv_len, q_pos,
                                     rank=LAT_RANK, scale=0.2)
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    seen = _seen(q_pos, kv_len)
    np.testing.assert_allclose(out[seen], want[seen], atol=TOL[dtype])
    assert not out[~seen].any()


@pytest.mark.parametrize("layer", [0, 3])
def test_paged_latent_attention_reads_stacked_pool_at_layer(layer):
    """The layer loop hands the kernel the stacked latent pool [L, NB, bs,
    P] viewed as [L * NB, bs, P] through tables offset to the layer
    (``attention.layer_pages``): it must equal the oracle on pool[layer]."""
    from repro.models.attention import layer_pages
    q, pages, tables, kv_len, q_pos = _latent_setup(2, 4, 9, 16, 4,
                                                    jnp.bfloat16, k=90,
                                                    layers=4)
    out = jax.jit(lambda i: ops.paged_latent_attention(
        q, *layer_pages(pages, tables, i), kv_len, q_pos, rank=LAT_RANK,
        scale=0.2))(jnp.int32(layer))
    want = ref.paged_latent_attention_ref(q, pages[layer], tables, kv_len,
                                          q_pos, rank=LAT_RANK, scale=0.2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_decode_attention_window():
    b, tq, h, d, s = 2, 3, 4, 32, 128
    q, k, v = rand(b, tq, h, d, k=7), rand(b, s, h, d, k=8), rand(b, s, h, d, k=9)
    kv_len = jnp.asarray([100, 80], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention(q, k, v, kv_len, q_pos, window=32, block_k=32)
    want = ref.decode_attention_ref(q, k, v, kv_len, q_pos, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("k_sub,r", [(3, 0.6), (4, 0.5)])
def test_pard_attention(k_sub, r):
    from repro.core.cod import CodConfig, pack_batch
    toks = np.random.default_rng(0).integers(0, 500, size=(2, 40))
    packed = pack_batch(toks, CodConfig(k=k_sub, r=r, r_min=0.2), 512, seed=0)
    seg = jnp.asarray(packed["segment"])
    base = jnp.asarray(packed["base"])
    t = seg.shape[1]
    q, k, v = rand(2, t, 2, 32, k=10), rand(2, t, 2, 32, k=11), rand(2, t, 2, 32, k=12)
    out = ops.pard_attention(q, k, v, seg, base, block_q=32)
    want = ref.pard_attention_ref(q, k, v, seg, base)
    live = np.asarray(seg > 0)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out) * live, np.asarray(want) * live,
                               atol=2e-5)


@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (1, 32, 2, 8, 4, 8), (2, 64, 3, 16, 8, 16), (1, 50, 2, 8, 8, 16),
])
def test_ssd_kernel(b, t, h, p, n, chunk):
    x = rand(b, t, h, p, k=13)
    dt = jax.nn.softplus(rand(b, t, h, k=14))
    A = -jnp.exp(rand(h, k=15) * 0.5)
    B = rand(b, t, n, k=16)
    C = rand(b, t, n, k=17)
    s0 = rand(b, h, p, n, k=18) * 0.1
    y, sf = ops.ssd_chunked(x, dt, A, B, C, s0, chunk=chunk)
    yr, sr = ref.ssd_ref(x, dt, A, B, C, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), atol=2e-4)


def test_ssd_kernel_matches_model_chunked_path():
    """The kernel and the model's jnp chunked scan must agree (they are the
    two production paths)."""
    from repro.models.ssm import ssd_scan_chunked
    b, t, h, p, n = 2, 48, 2, 8, 8
    x = rand(b, t, h, p, k=19)
    dt = jax.nn.softplus(rand(b, t, h, k=20))
    A = -jnp.exp(rand(h, k=21) * 0.5)
    B, C = rand(b, t, n, k=22), rand(b, t, n, k=23)
    y1, s1 = ops.ssd_chunked(x, dt, A, B, C, chunk=16)
    y2, s2 = ssd_scan_chunked(x, dt, A, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4)
