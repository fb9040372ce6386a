"""Paged KV pool unit tests: allocator invariants (DESIGN.md §5 I1-I4),
paged write/gather semantics, the pool's per-layer leaves, forwards over
the pool against the cache-free forward, and pool bytes accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_params
from repro.models import ssm as ssm_mod
from repro.models.attention import (gather_pages, mla_pool_width,
                                    resolve_kv_dtype, write_cache_paged)
from repro.models.config import (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, SSM,
                                 scan_plan)
from repro.serving import kv_pool

from hlo_text import pool_shapes, whole_buffer_fills_and_copies


# ---------------------------------------------------------------------- I1/I2
def test_allocator_reserves_garbage_block():
    a = kv_pool.BlockAllocator(num_blocks=9, block_size=16, max_batch=2,
                               max_len=64)
    a.allocate(0, 64)
    a.allocate(1, 64)
    assert len(a.free) == 0                      # 8 usable blocks handed out
    used = a.owned[0] + a.owned[1]
    assert 0 not in used                         # I1: block 0 never allocated
    assert len(set(used)) == len(used)           # I2: unique ownership


def test_allocator_blocks_needed_rounding():
    a = kv_pool.BlockAllocator(num_blocks=32, block_size=16, max_batch=2,
                               max_len=256)
    assert a.blocks_needed(1) == 1
    assert a.blocks_needed(16) == 1
    assert a.blocks_needed(17) == 2
    # I3: an allocation a sequence's table cannot cover must fail loudly,
    # never clamp (a short allocation would let decode attend garbage KV)
    with pytest.raises(ValueError, match="block"):
        a.allocate(0, 10_000)


def test_allocator_release_reuses_blocks_and_zeroes_table():
    a = kv_pool.BlockAllocator(num_blocks=5, block_size=16, max_batch=2,
                               max_len=64)
    a.allocate(0, 60)                            # all 4 usable blocks
    first = list(a.owned[0])
    assert not a.can_allocate(1)                 # backpressure point
    v0 = a.version
    freed = a.release(0)
    assert sorted(freed) == sorted(first)
    assert np.all(a.tables[0] == 0)              # I4: row zeroed on release
    assert a.version > v0                        # device copy refresh signal
    a.allocate(1, 60)
    assert sorted(a.owned[1]) == sorted(first)   # freed blocks reallocated


def test_write_then_gather_roundtrip():
    """write_cache_paged + gather_pages reproduce a contiguous cache for
    arbitrary (interleaved) block tables."""
    bs, nb, mbs, b, h, d = 8, 7, 3, 2, 2, 4
    pages = jnp.zeros((nb, bs, h, d), jnp.float32)
    # deliberately non-monotone block ownership
    tables = jnp.asarray([[3, 1, 5], [2, 6, 4]], jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(0), (b, 11, h, d))
    pages = write_cache_paged(pages, new[:, :5], jnp.zeros((b,), jnp.int32),
                              tables, bs)
    pages = write_cache_paged(pages, new[:, 5:], jnp.full((b,), 5, jnp.int32),
                              tables, bs)
    view = gather_pages(pages, tables)           # [B, 24, h, d]
    np.testing.assert_allclose(np.asarray(view[:, :11]), np.asarray(new))
    assert np.all(np.asarray(view[:, 11:]) == 0.0)


def test_write_past_allocation_lands_in_garbage_block():
    bs, nb = 8, 4
    pages = jnp.zeros((nb, bs, 1, 2), jnp.float32)
    tables = jnp.asarray([[2, 0, 0]], jnp.int32)  # 1 block allocated
    new = jnp.ones((1, 6, 1, 2))
    # write straddles the allocation boundary: positions 5..7 -> block 2,
    # 8..10 -> unallocated entry -> garbage block 0 (I1)
    pages = write_cache_paged(pages, new, jnp.full((1,), 5, jnp.int32),
                              tables, bs)
    assert np.all(np.asarray(pages[2, 5:8]) == 1.0)
    assert np.all(np.asarray(pages[0, 0:3]) == 1.0)   # garbage block absorbed
    assert np.all(np.asarray(pages[1]) == 0.0)        # other blocks untouched
    assert np.all(np.asarray(pages[3]) == 0.0)
    # positions past the END of the table (ent >= MBS) also route to the
    # garbage block — never into the row's last real block
    far = write_cache_paged(jnp.zeros((nb, bs, 1, 2)), 7 * jnp.ones((1, 2, 1, 2)),
                            jnp.full((1,), 3 * bs + 2, jnp.int32), tables, bs)
    assert np.all(np.asarray(far[0, 2:4]) == 7.0)
    assert np.all(np.asarray(far[1:]) == 0.0)


def _chunked_pool_logits(params, cfg, tokens, dtype, chunks):
    """Logits of ``tokens`` [2, T] fed through a KV pool in consecutive
    windows ``chunks`` ((start, end) pairs): a prefill chunk, a second one
    and a decode step, each writing and reading through scrambled block
    tables (the rows own interleaved, non-monotone pages)."""
    bs = 8
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(np.arange(1, 9)).reshape(2, 4),
        jnp.int32)
    pool = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=bs,
                                     dtype=dtype)
    outs = []
    for a, b in chunks:
        lg, pool, _ = forward(params, cfg, tokens[:, a:b], caches=pool,
                              cache_pos=jnp.full(2, a, jnp.int32),
                              block_tables=tables, kv_block_size=bs,
                              dtype=jnp.float32)
        outs.append(lg)
    return jnp.concatenate(outs, axis=1)


def _cache_free_logits(params, cfg, tokens):
    """The uncached forward over the whole sequence: attention over the
    forward's own keys, SSM scans from the zero state. ``cache_pos`` (no
    caches) selects the dropless MoE routing every cached forward uses."""
    logits, _, _ = forward(params, cfg, tokens,
                           cache_pos=jnp.zeros(2, jnp.int32),
                           dtype=jnp.float32)
    return logits


CHUNKS = ((0, 8), (8, 12), (12, 13))


@pytest.mark.parametrize("arch", ["tiny-target", "jamba-1.5-large-398b-smoke",
                                  "deepseek-v2-lite-16b-smoke"])
def test_forward_layout_equivalence(arch):
    """Prefill chunks + decode through a pool with scrambled block
    ownership give the logits of the cache-free forward (up to numerics) —
    covers the GQA, MLA and SSM-hybrid cache paths."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0,
                                cfg.vocab_size)
    out = _chunked_pool_logits(params, cfg, tokens, jnp.float32, CHUNKS)
    want = _cache_free_logits(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def _leaf_shapes(cfg, spec, batch, nb, bs, quant):
    """The documented per-layer pool leaves (models.init_caches)."""
    if spec.mixer in (ATTN_GLOBAL, ATTN_LOCAL):
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        out = {"k": (nb, bs, hkv, hd), "v": (nb, bs, hkv, hd)}
        if quant:
            out.update(k_scale=(nb, bs, hkv), v_scale=(nb, bs, hkv))
        return out
    if spec.mixer == ATTN_MLA:
        # the latent row [c_kv | k_rope], zero-padded to whole 128 lanes
        width = mla_pool_width(cfg)
        assert width % 128 == 0
        assert 0 <= width - cfg.kv_lora_rank - cfg.qk_rope_head_dim < 128
        out = {"ckv": (nb, bs, width)}
        if quant:
            out["ckv_scale"] = (nb, bs)
        return out
    if spec.mixer == SSM:        # per row, [batch, ...]
        return {k: v.shape for k, v in
                ssm_mod.init_mamba2_state(cfg, batch, jnp.float32).items()}
    return {}                    # cross attention keeps no pool


def _assert_pool_follows_scan_plan(cfg, dtype, quant):
    nb, bs, batch = 9, 8, 2
    pool = kv_pool.init_paged_caches(cfg, batch, num_blocks=nb,
                                     block_size=bs, dtype=dtype)
    plan = scan_plan(cfg)
    assert len(pool["prefix"]) == len(plan.prefix)
    assert len(pool["scan"]) == len(plan.period)
    for entries, specs, lead in ((pool["prefix"], plan.prefix, ()),
                                 (pool["scan"], plan.period,
                                  (plan.n_repeats,))):
        for entry, spec in zip(entries, specs):
            want = _leaf_shapes(cfg, spec, batch, nb, bs, quant)
            assert {k: v.shape for k, v in entry.items()} == {
                k: lead + v for k, v in want.items()}, spec
            for name, leaf in entry.items():
                if name in ("k", "v", "ckv"):
                    assert leaf.dtype == resolve_kv_dtype(dtype)
                else:            # quant scales and SSM states
                    assert leaf.dtype == jnp.float32
    cap = kv_pool.kv_capacity_bytes(cfg, pool)
    assert cap == kv_pool.kv_bytes_per_block(cfg, pool, nb) * nb > 0
    return cap


@pytest.mark.parametrize("arch", ["tiny-target", "jamba-1.5-large-398b-smoke",
                                  "deepseek-v2-lite-16b-smoke"])
def test_pool_leaves_follow_scan_plan(arch):
    """Every layer of ``scan_plan`` gets its documented cache: GQA K/V and
    MLA latent pools [(R,) NB, bs, ...] shared through the block tables,
    SSM states per row; byte accounting covers exactly the pools."""
    _assert_pool_follows_scan_plan(get_config(arch), jnp.float32, False)


# ------------------------------------------------------------ quantized pools
@pytest.mark.parametrize("arch", ["tiny-target",
                                  "deepseek-v2-lite-16b-smoke"])
def test_forward_layout_equivalence_int8(arch):
    """The pool contract survives quantization, covering GQA (fused-kernel
    dequant) and MLA (dequant-at-gather). Quantization is deterministic
    per write, so the chunk schedule changes nothing: chunked prefill +
    decode give the logits of one window over the whole sequence. Against
    the cache-free forward the int8 K/V error (at most amax/254 an element)
    stays within 5% of the largest logit; a misread encoding or scale
    errs by the logits' own size."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0,
                                cfg.vocab_size)
    out = _chunked_pool_logits(params, cfg, tokens, "int8", CHUNKS)
    whole = _chunked_pool_logits(params, cfg, tokens, "int8", ((0, 13),))
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               atol=2e-4, rtol=2e-4)
    want = np.asarray(_cache_free_logits(params, cfg, tokens))
    v = cfg.vocab_size                      # padded ids hold -inf-like fill
    scale = np.abs(want[..., :v]).max()
    err = np.abs(np.asarray(out)[..., :v] - want[..., :v]).max()
    assert 0 < err <= 0.05 * scale, (err, scale)


@pytest.mark.parametrize("arch", ["tiny-target",
                                  "deepseek-v2-lite-16b-smoke"])
def test_quant_pool_leaves_follow_scan_plan(arch):
    """Quantized pools keep the same per-layer plan, with float32 dequant
    scales beside the values, and hold at most half the fp32 pool's
    bytes."""
    cfg = get_config(arch)
    cap = _assert_pool_follows_scan_plan(cfg, "int8", True)
    assert cap * 2 <= _assert_pool_follows_scan_plan(cfg, "fp32", False)


def test_quant_write_past_allocation_lands_in_garbage_block():
    """I1 under quantization: both the value write AND the scale write for
    positions past the allocation route to garbage block 0."""
    bs, nb = 8, 4
    pages = jnp.zeros((nb, bs, 1, 2), jnp.int8)
    scales = jnp.ones((nb, bs, 1), jnp.float32)
    tables = jnp.asarray([[2, 0, 0]], jnp.int32)     # 1 block allocated
    newq = jnp.ones((1, 6, 1, 2), jnp.int8)
    news = jnp.full((1, 6, 1), 3.0, jnp.float32)
    pages = write_cache_paged(pages, newq, jnp.full((1,), 5, jnp.int32),
                              tables, bs)
    scales = write_cache_paged(scales, news, jnp.full((1,), 5, jnp.int32),
                               tables, bs)
    assert np.all(np.asarray(pages[2, 5:8]) == 1)
    assert np.all(np.asarray(scales[2, 5:8]) == 3.0)
    assert np.all(np.asarray(pages[0, 0:3]) == 1)    # garbage block absorbed
    assert np.all(np.asarray(scales[0, 0:3]) == 3.0)
    assert np.all(np.asarray(pages[1]) == 0)         # other blocks untouched
    assert np.all(np.asarray(scales[1]) == 1.0)


# ------------------------------------------------- pools updated in place
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["flat", "tree"])
def test_fused_step_updates_stacked_pools_in_place(kind, kv):
    """The paged pools of the scanned layers ride the layer loop's carry:
    the compiled serving step writes each layer's KV into the donated pool
    in place. No broadcast (the zero fill of a fresh loop output) and no
    copy (loop output into the returned state) of a whole stacked pool
    leaf, K, V or their scales, of either model may appear in it."""
    from repro.serving.engine import Engine, EngineConfig
    tc, dc = get_config("tiny-target"), get_config("tiny-draft")
    eng = Engine(init_params(jax.random.PRNGKey(0), tc), tc,
                 init_params(jax.random.PRNGKey(1), dc), dc,
                 config=EngineConfig(
                     mode="pard", k=4, max_batch=2, max_len=256,
                     kv_block_size=16, kv_dtype=kv,
                     tree=(2, 2, 1) if kind == "tree" else None))
    shapes = pool_shapes(eng.ex.state)
    assert len(shapes) == (2 if kv == "bf16" else 4)
    text = eng.ex.step_hlo(tree=kind == "tree")
    assert whole_buffer_fills_and_copies(text, shapes) == []


def test_row_tables_give_each_row_its_own_pages():
    """The uniform-batch block tables of ``generate_*`` are the ones the
    serving allocator hands out: every row owns the pages covering its positions,
    no page twice, none of them the garbage block; the rest of each row's
    table points at the garbage block (I1, I2)."""
    from repro.core.spec_decode import row_tables
    nb, tables = row_tables(3, 40, 128, 16)
    tables = np.asarray(tables)
    assert tables.shape == (3, 8)                    # blocks_for(128, 16)
    assert nb == 3 * 3 + 1                           # 40 tokens: 3 pages
    owned = tables[:, :3]
    assert 0 not in owned
    assert len(set(owned.ravel())) == owned.size
    assert set(owned.ravel()) == set(range(1, nb))
    assert np.all(tables[:, 3:] == 0)
    # exactly the tables a fresh serving allocator hands out row after row
    alloc = kv_pool.BlockAllocator(nb, 16, 3, 128)
    for row in range(3):
        alloc.allocate(row, 40)
    np.testing.assert_array_equal(tables, alloc.tables)


def test_cached_forward_requires_block_tables():
    """The pool is the only cache: a cached forward without block tables
    and a page size is refused, not read as some other layout."""
    cfg = get_config("tiny-target")
    params = init_params(jax.random.PRNGKey(0), cfg)
    pool = kv_pool.init_paged_caches(cfg, 1, 3, 8)
    with pytest.raises(AssertionError, match="block_tables"):
        forward(params, cfg, jnp.zeros((1, 4), jnp.int32), caches=pool,
                cache_pos=jnp.zeros(1, jnp.int32))
