"""Per-request tree templates and acceptance-driven reshaping (DESIGN.md
§7): TemplateBank construction, mixed-template batches staying lossless
(greedy rows token-identical to AR, sampled rows to generate_spec on the
same row key), per-request paged allocation sizing (no over/under-allocation when a wide and a chain request
share one batch), the submit() feasibility error path, allocator growth,
the EWMA controller's selection policy, and per-row win_len parity of the
tree-attention kernels against their oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.spec_decode import SpecDecoder, TemplateBank
from repro.kernels import ops, ref
from repro.models import init_params
from repro.serving.engine import Engine, TreeController
from repro.serving.kv_pool import BlockAllocator

from decode_refs import greedy_reference, sampled_reference


@pytest.fixture(scope="module")
def tiny():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _prompt(vocab, b=2, p=8, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, p), 0, vocab)


def _ragged_prompts(n, seed=21):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=int(t)).astype(np.int32)
            for t in rng.integers(4, 14, size=n)]


BANK = ((1, 1, 1, 1), (2, 2, 2, 1), (4, 2, 1, 1))


# ---------------------------------------------------------------- bank
def test_template_bank_construction():
    bank = TemplateBank.from_templates(BANK)
    assert len(bank) == 3 and bank.max_depth == 4
    assert bank.max_slots == 29 and bank.max_branching == 4
    assert list(bank.nslots) == [5, 23, 29]
    # padded slots carry zeroed metadata beyond each template's slot count
    for i, t in enumerate(bank.templates):
        ns = t.num_slots
        assert np.array_equal(bank.anc[i, :ns], t.anc)
        assert not bank.anc[i, ns:].any()
        assert not bank.depth[i, ns:].any()
    # the default bank's wide hedge stays within the balanced tree's
    # padded window (22 <= 23 slots) — see TemplateBank.default
    assert TemplateBank.default(4).key == "1x1x1x1|2x2x2x1|3x2x1x1"
    assert TemplateBank.default(4).max_slots == 23


def test_template_bank_rejects_mixed_depth():
    with pytest.raises(AssertionError, match="share one depth"):
        TemplateBank.from_templates(((1, 1, 1, 1), (2, 2)))


def test_row_slack_per_template(tiny):
    tc, tp, dc, dp = tiny
    dec = SpecDecoder(tp, tc, dp, dc, max_len=256,
                      tree=TemplateBank.from_templates(BANK))
    # chain: draft window 2K=8 dominates its 5 slots; wide: 29 slots win
    assert dec.row_slack(0) == 10
    assert dec.row_slack(1) == 25
    assert dec.row_slack(2) == 31
    assert dec.window_slack == 31 and dec.min_row_slack == 10


# ---------------------------------------------- mixed-template batches
def test_mixed_template_batch_lossless(tiny):
    """One generate_spec batch where every row uses a DIFFERENT bank
    template must stay token-identical to AR for every row."""
    tc, tp, dc, dp = tiny
    bank = TemplateBank.from_templates(BANK)
    dec = SpecDecoder(tp, tc, dp, dc, max_len=256, tree=bank)
    prompt = _prompt(tc.vocab_size, b=3)
    ar, _ = dec.generate_ar(prompt, 32)
    sp, stats = dec.generate_spec(prompt, 32, mode="pard",
                                  tree_idx=[0, 1, 2])
    assert bool(jnp.all(ar == sp))
    assert stats.tokens_generated == 32 * 3


def test_mixed_batch_chain_row_identical_to_flat(tiny):
    """A chain-template row inside a mixed batch must reproduce the flat-K
    PARD path token for token — per-row masks and win_len fully isolate it
    from the wide-template rows sharing the batch window."""
    tc, tp, dc, dp = tiny
    prompt = _prompt(tc.vocab_size, b=2)
    flat = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    ref_toks, _ = flat.generate_spec(prompt, 32, mode="pard")
    bank = TemplateBank.from_templates(BANK)
    mixed = SpecDecoder(tp, tc, dp, dc, max_len=256, tree=bank)
    out, _ = mixed.generate_spec(prompt, 32, mode="pard", tree_idx=[0, 2])
    assert bool(jnp.all(ref_toks[0] == out[0]))


def test_engine_mixed_templates_match_ar(tiny):
    """Wide-template and chain requests SHARING one paged batch: every
    completion must match its single-request AR reference (self-draft so
    different shapes really accept different paths)."""
    tc, tp, dc, dp = tiny
    prompts = _ragged_prompts(5)
    refs = {}
    for i, p in enumerate(prompts):
        dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
        refs[i] = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 12)[0][0])
    eng = Engine(tp, tc, tp, tc, mode="pard", max_batch=2, max_len=256,
                 kv_block_size=32,
                 tree=TemplateBank.from_templates(BANK))
    rids = {eng.submit(p, 12, tree_idx=i % 3): i
            for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    for c in comps:
        assert np.array_equal(refs[rids[c.rid]], c.tokens)
    assert eng.mean_accepted() > 1.0


def test_engine_mixed_templates_layouts_agree(tiny):
    """Mixed per-request templates with a separate draft commit each
    request's greedy AR tokens through the KV pool."""
    tc, tp, dc, dp = tiny
    prompts = _ragged_prompts(4, seed=22)
    eng = Engine(tp, tc, dp, dc, mode="pard", max_batch=2, max_len=256,
                 kv_block_size=32, tree=TemplateBank.from_templates(BANK))
    rids = {eng.submit(p, 12, tree_idx=(i * 2) % 3): i
            for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    for c in comps:
        assert np.array_equal(
            greedy_reference(dec, prompts[rids[c.rid]], 12), c.tokens)


def test_engine_mixed_templates_sampled_layouts_agree(tiny):
    """Per-request templates + per-request temperature: greedy rows commit
    their AR tokens, and sampled rows the tokens of ``generate_spec`` on
    the same (seed, rid) key and template — seeded determinism with mixed
    tree shapes in one batch."""
    tc, tp, dc, dp = tiny
    prompts = _ragged_prompts(4, seed=23)
    bank = TemplateBank.from_templates(BANK)
    eng = Engine(tp, tc, tp, tc, mode="pard", max_batch=2, max_len=256,
                 temperature=0.8, seed=5, kv_block_size=32, tree=bank)
    rids = {}
    for i, p in enumerate(prompts):
        t = 0.0 if i % 2 == 0 else None
        rids[eng.submit(p, 12, temperature=t, tree_idx=i % 3)] = i
    comps = eng.run()
    assert len(comps) == len(prompts)
    ar = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
    sampler = SpecDecoder(tp, tc, tp, tc, max_len=256, temperature=0.8,
                          kv_block_size=32, tree=bank)
    for c in comps:
        i = rids[c.rid]
        if i % 2 == 0:
            want = greedy_reference(ar, prompts[i], 12)
        else:
            want = sampled_reference(sampler, prompts[i], 12, c.rid, 5,
                                     tree_idx=i % 3)
        assert np.array_equal(want, c.tokens)


# ------------------------------------------------- per-request sizing
def test_per_request_block_allocation(tiny):
    """A chain request and a wide-template request admitted into one paged
    engine must allocate blocks for their OWN window slack — the chain row
    strictly fewer — and both must still match their AR references (no
    under-allocation: every slot a row actually reads is backed)."""
    tc, tp, dc, dp = tiny
    bank = TemplateBank.from_templates(BANK)
    p_len, max_new, bs = 8, 12, 32
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, 512, size=p_len).astype(np.int32)
               for _ in range(2)]
    eng = Engine(tp, tc, tp, tc, mode="pard", max_batch=2, max_len=256,
                 kv_block_size=bs, tree=bank)
    allocs = {}

    def spy(slot, n, _orig=eng.alloc.allocate):
        _orig(slot, n)
        allocs[slot] = (n, len(eng.alloc.owned[slot]))

    eng.alloc.allocate = spy
    rids = {eng.submit(prompts[0], max_new, tree_idx=0): 0,   # chain
            eng.submit(prompts[1], max_new, tree_idx=2): 1}   # wide
    comps = eng.run()
    dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
    for c in comps:
        i = rids[c.rid]
        ref_toks = np.asarray(
            dec.generate_ar(jnp.asarray(prompts[i])[None], max_new)[0][0])
        assert np.array_equal(ref_toks, c.tokens)
    # exact per-template sizing: prompt + max_new + row_slack, no more
    need_chain = p_len + max_new + 10          # max(2K, 5) + 2
    need_wide = p_len + max_new + 31           # max(2K, 29) + 2
    assert allocs[0] == (need_chain, -(-need_chain // bs))
    assert allocs[1] == (need_wide, -(-need_wide // bs))
    assert allocs[1][1] > allocs[0][1]


def test_submit_feasibility_uses_per_request_slack(tiny):
    """The submit() need-vs-max_len error path with per-request slack: a
    prompt that fits the chain template but not the wide one is accepted
    unpinned (admission restricts itself to feasible templates), accepted
    pinned to the chain, and rejected pinned to the wide template."""
    tc, tp, dc, dp = tiny
    bank = TemplateBank.from_templates(BANK)
    eng = Engine(tp, tc, tp, tc, mode="pard", max_batch=1, max_len=64,
                 kv_block_size=32, tree=bank)
    prompt = np.arange(10, dtype=np.int32) % 512
    # 10 + 32 + 31 (wide) = 73 > 64, but + 10 (chain) = 52 fits
    with pytest.raises(ValueError, match="cache positions"):
        eng.submit(prompt, 32, tree_idx=2)
    eng.submit(prompt, 32, tree_idx=0)
    eng.submit(prompt, 32)                     # unpinned: feasible subset
    comps = eng.run()
    assert len(comps) == 2
    assert all(c.generated == 32 for c in comps)
    # invalid template index fails loudly too
    with pytest.raises(ValueError, match="tree_idx"):
        eng.submit(prompt, 8, tree_idx=7)


def test_block_allocator_grow():
    alloc = BlockAllocator(num_blocks=8, block_size=16, max_batch=2,
                           max_len=128)
    alloc.allocate(0, 30)                      # 2 blocks
    owned = list(alloc.tables[0, :2])
    v0 = alloc.version
    assert alloc.grow(0, 20) and alloc.version == v0       # no-op: covered
    assert alloc.grow(0, 60)                   # 4 blocks now
    assert len(alloc.owned[0]) == 4
    assert list(alloc.tables[0, :2]) == owned  # prefix untouched
    assert alloc.tables[0, 2] != 0 and alloc.tables[0, 3] != 0
    assert alloc.version == v0 + 1
    alloc.allocate(1, 48)                      # 3 blocks -> pool exhausted
    assert not alloc.grow(0, 100)              # would need 3 more; 0 free
    assert len(alloc.owned[0]) == 4            # refusal left it untouched
    alloc.release(0)
    assert len(alloc.free) == 4


# ----------------------------------------------------- the controller
def test_controller_prefers_deep_chain_for_rank0_acceptance():
    """Synthetic stats: rank 0 accepts almost always at every depth, extra
    ranks never — the deep chain maximises expected accepted length."""
    bank = TemplateBank.from_templates(BANK)
    ctrl = TreeController(bank, max_batch=1, ewma=0.5)
    live = np.array([True])
    tree_idx = np.array([2], np.int32)         # wide in use: ranks offered
    rank = np.zeros((1, 4), np.int32)          # rank 0 wins every depth
    for _ in range(60):
        ctrl.update(live, tree_idx, np.array([4]), rank)
    assert ctrl.select(slot=0) == 0            # the chain


def test_controller_prefers_wide_for_rank_spread_acceptance():
    """Synthetic stats: depth 1 accepts only via ranks >= 1 (the target
    argmax lands in top-4 but rarely top-1) and nothing deeper — hedging
    wide at depth 1 beats the chain."""
    bank = TemplateBank.from_templates(BANK)
    ctrl = TreeController(bank, max_batch=1, ewma=0.5)
    live = np.array([True])
    tree_idx = np.array([2], np.int32)
    for i in range(60):
        rank = np.full((1, 4), -1, np.int32)
        rank[0, 0] = 1 + (i % 3)               # ranks 1..3 win depth 1
        ctrl.update(live, tree_idx, np.array([1]), rank)
    assert ctrl.select(slot=0) == 2            # the wide template


def test_adaptive_admission_falls_back_to_pool_sized_template(tiny):
    """A pool sized for the chain template only: the controller's
    optimistic prior would pick a wider tree than the free list can back —
    admission must fall back to the narrowest feasible template and serve
    the request rather than head-of-line block or crash run()."""
    tc, tp, dc, dp = tiny
    rng = np.random.default_rng(26)
    prompt = rng.integers(0, 512, size=8).astype(np.int32)
    # chain need = 8+16+10 = 34 -> 5 blocks of 8; wide needs 7 of 6 usable
    eng = Engine(tp, tc, tp, tc, mode="pard", max_batch=1, max_len=128,
                 kv_block_size=8, kv_num_blocks=7,
                 adaptive_tree=True, tree=TemplateBank.from_templates(BANK))
    eng.submit(prompt, 16)
    comps = eng.run()
    assert len(comps) == 1 and comps[0].generated == 16
    dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=128)
    ref_toks = np.asarray(
        dec.generate_ar(jnp.asarray(prompt)[None], 16)[0][0])
    assert np.array_equal(ref_toks, comps[0].tokens)


def test_adaptive_engine_lossless_and_accounted(tiny):
    """Greedy losslessness is template-independent, so the adaptive engine
    must match per-request AR references NO MATTER what the controller
    selects or when it reshapes; tree_hist accounts every live step to the
    then-active template."""
    tc, tp, dc, dp = tiny
    prompts = _ragged_prompts(5, seed=25)
    refs = {}
    for i, p in enumerate(prompts):
        dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
        refs[i] = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 12)[0][0])
    eng = Engine(tp, tc, tp, tc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32, adaptive_tree=True,
                 tree_reselect_every=2)
    rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    for c in comps:
        assert np.array_equal(refs[rids[c.rid]], c.tokens)
    assert int(eng.stats["tree_hist"].sum()) == eng.stats["live_steps"]
    assert eng.mean_accepted() > 1.5           # self-draft accepts deeply


# ------------------------------------------------ kernels: win_len
def _qkv(rng, b, tq, s, hq=4, hkv=2, d=16):
    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return r(b, tq, hq, d), r(b, s, hkv, d), r(b, s, hkv, d)


def _random_anc(rng, b, tq):
    bits = rng.integers(0, 2, size=(b, tq, tq)).astype(np.uint32)
    anc = np.zeros((b, tq), np.uint32)
    for sl in range(tq):
        bits[:, sl, sl] = 1
        anc[:, sl] = sum(bits[:, sl, j].astype(np.uint32) << np.uint32(j)
                         for j in range(tq))
    return jnp.asarray(anc)


def test_tree_attention_per_row_win_len_matches_ref():
    rng = np.random.default_rng(0)
    b, tq, s = 3, 8, 128
    q, k, v = _qkv(rng, b, tq, s)
    win_start = jnp.asarray([40, 25, 60], jnp.int32)
    kv_len = win_start + tq
    q_pos = win_start[:, None] + jnp.arange(tq)[None, :]
    anc = _random_anc(rng, b, tq)
    win_len = jnp.asarray([3, 8, 5], jnp.int32)    # per-row window sizing
    out = ops.tree_attention(q, k, v, kv_len, q_pos, win_start, anc,
                             win_len=win_len, interpret=True)
    want = ref.tree_attention_ref(q, k, v, kv_len, q_pos, win_start, anc,
                                  win_len=win_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # a row with win_len == tq must equal the no-win_len call exactly
    full = ops.tree_attention(q, k, v, kv_len, q_pos, win_start, anc,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(full[1]),
                               rtol=2e-5, atol=2e-5)


def test_tree_attention_paged_per_row_win_len_matches_ref():
    rng = np.random.default_rng(1)
    b, tq, bs, mbs = 2, 8, 32, 6
    nb = 1 + b * mbs
    q = jnp.asarray(rng.standard_normal((b, tq, 4, 16)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((nb, bs, 2, 16)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((nb, bs, 2, 16)), jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(b * mbs, dtype=np.int32).reshape(b, mbs))
    win_start = jnp.asarray([100, 70], jnp.int32)
    kv_len = win_start + tq
    q_pos = win_start[:, None] + jnp.arange(tq)[None, :]
    anc = _random_anc(rng, b, tq)
    win_len = jnp.asarray([2, 6], jnp.int32)
    out = ops.tree_attention_paged(q, k_pages, v_pages, tables, kv_len,
                                   q_pos, win_start, anc, win_len=win_len,
                                   interpret=True)
    want = ref.tree_attention_paged_ref(q, k_pages, v_pages, tables, kv_len,
                                        q_pos, win_start, anc,
                                        win_len=win_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
