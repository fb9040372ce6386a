"""The absorbed latent (MLA) attention kernel over a paged latent pool
against its ref.py oracle (interpret mode on the CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

from kernel_inputs import TOL, rand, seen_queries, sweep_fills


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
    """Rows of every edge fill in one call (``sweep_fills``), 64-slot pages
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
    fills = sweep_fills(bs, group, mbs)[::-1 if reverse else 1]
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
    seen = seen_queries(q_pos, kv_len)
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
