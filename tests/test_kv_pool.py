"""Paged KV pool unit tests: allocator invariants (DESIGN.md §5 I1-I4),
paged write/gather semantics, and pool bytes accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_caches, init_params
from repro.models.attention import gather_pages, write_cache_paged
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


@pytest.mark.parametrize("arch", ["tiny-target", "jamba-1.5-large-398b-smoke",
                                  "deepseek-v2-lite-16b-smoke"])
def test_forward_layout_equivalence(arch):
    """Prefill + decode logits must be identical (up to numerics) between
    contiguous caches and a paged pool with scrambled block ownership —
    covers the GQA, MLA and SSM-hybrid cache paths."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    bs, mbs = 8, 4
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(np.arange(1, 9)).reshape(2, 4),
        jnp.int32)

    cont = init_caches(cfg, 2, bs * mbs, dtype=jnp.float32)
    _, cont, _ = forward(params, cfg, tokens, caches=cont,
                         cache_pos=jnp.zeros(2, jnp.int32), dtype=jnp.float32)
    want, _, _ = forward(params, cfg, tokens[:, -1:], caches=cont,
                         cache_pos=jnp.full(2, 12, jnp.int32),
                         dtype=jnp.float32)

    paged = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=bs,
                                      dtype=jnp.float32)
    _, paged, _ = forward(params, cfg, tokens, caches=paged,
                          cache_pos=jnp.zeros(2, jnp.int32),
                          block_tables=tables, kv_block_size=bs,
                          dtype=jnp.float32)
    out, _, _ = forward(params, cfg, tokens[:, -1:], caches=paged,
                        cache_pos=jnp.full(2, 12, jnp.int32),
                        block_tables=tables, kv_block_size=bs,
                        dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["tiny-target", "jamba-1.5-large-398b-smoke",
                                  "deepseek-v2-lite-16b-smoke"])
def test_paged_cache_structure_matches_contiguous(arch):
    """Same pytree structure as init_caches (the engine swaps layouts
    without touching any consumer); attention leaves paged, SSM unchanged."""
    cfg = get_config(arch)
    cont = init_caches(cfg, 2, 64, dtype=jnp.float32)
    paged = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=8,
                                      dtype=jnp.float32)
    assert (jax.tree.structure(cont) == jax.tree.structure(paged))
    cap = kv_pool.kv_capacity_bytes(cfg, paged)
    per = kv_pool.kv_bytes_per_block(cfg, paged, 9)
    assert cap == per * 9 > 0


# ------------------------------------------------------------ quantized pools
@pytest.mark.parametrize("arch", ["tiny-target",
                                  "deepseek-v2-lite-16b-smoke"])
def test_forward_layout_equivalence_int8(arch):
    """The layout contract survives quantization: contiguous int8 caches
    and a scrambled int8 paged pool see the SAME appended encodings
    (quantization is deterministic per write), so decode logits agree to
    the usual layout tolerance — covers GQA (fused-kernel dequant) and
    MLA (dequant-at-gather) cache paths."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    bs, mbs = 8, 4
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(np.arange(1, 9)).reshape(2, 4),
        jnp.int32)

    cont = init_caches(cfg, 2, bs * mbs, dtype="int8")
    _, cont, _ = forward(params, cfg, tokens, caches=cont,
                         cache_pos=jnp.zeros(2, jnp.int32), dtype=jnp.float32)
    want, _, _ = forward(params, cfg, tokens[:, -1:], caches=cont,
                         cache_pos=jnp.full(2, 12, jnp.int32),
                         dtype=jnp.float32)

    paged = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=bs,
                                      dtype="int8")
    _, paged, _ = forward(params, cfg, tokens, caches=paged,
                          cache_pos=jnp.zeros(2, jnp.int32),
                          block_tables=tables, kv_block_size=bs,
                          dtype=jnp.float32)
    out, _, _ = forward(params, cfg, tokens[:, -1:], caches=paged,
                        cache_pos=jnp.full(2, 12, jnp.int32),
                        block_tables=tables, kv_block_size=bs,
                        dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["tiny-target",
                                  "deepseek-v2-lite-16b-smoke"])
def test_quant_paged_cache_structure_matches_contiguous(arch):
    """The layout swap stays transparent under quantization: contiguous
    int8 caches (with their *_scale leaves) and the int8 paged pool share
    one pytree structure, and byte accounting covers values AND scales."""
    cfg = get_config(arch)
    cont = init_caches(cfg, 2, 64, dtype="int8")
    paged = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=8,
                                      dtype="int8")
    assert (jax.tree.structure(cont) == jax.tree.structure(paged))
    cap = kv_pool.kv_capacity_bytes(cfg, paged)
    per = kv_pool.kv_bytes_per_block(cfg, paged, 9)
    assert cap == per * 9 > 0
    fp32 = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=8,
                                     dtype="fp32")
    assert cap * 2 <= kv_pool.kv_capacity_bytes(cfg, fp32)


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
