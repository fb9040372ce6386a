"""Prefix-cache invariants (kv_pool I1/I2/I5) and engine-level identity:
refcounted sharing, computed-gated matching, LRU eviction, copy-on-write,
and cache-hit completions token-identical to cold ones."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import kv_pool
from repro.serving.kv_pool import BlockAllocator, prefix_block_keys


# --------------------------------------------------------------- invariants
def check_invariants(a: BlockAllocator):
    """Every structural invariant the allocator must hold between ops."""
    # I1: the garbage block is never handed out, cached, or refcounted
    assert 0 not in a.free and 0 not in a.lru and a.ref[0] == 0
    for blocks in a.owned.values():
        assert 0 not in blocks
    # refcount == number of slots mapping the block; never negative
    counts = {}
    for blocks in a.owned.values():
        for b in blocks:
            counts[b] = counts.get(b, 0) + 1
    for b in range(a.num_blocks):
        assert a.ref[b] == counts.get(b, 0), f"ref drift on block {b}"
    # never free/evictable while mapped ("never free a block with ref > 0")
    for b in a.free:
        assert a.ref[b] == 0
    for b in a.lru:
        assert a.ref[b] == 0
    assert not set(a.free) & set(a.lru)
    # I5: only computed registered blocks park on the LRU
    for b in a.lru:
        assert b in a.block_key and b in a.computed
    # index <-> block_key is a bijection where defined
    for key, b in a.index.items():
        assert a.block_key.get(b) == key
    for b, key in a.block_key.items():
        assert a.index.get(key) == b
    assert a.computed <= set(a.block_key)
    # I2: a block mapped by >= 2 slots is shared READ-ONLY — at most one
    # mapper (the original prefiller) holds it outside its read-only set,
    # and it must be computed (matching is gated on computed)
    for b, c in counts.items():
        if c >= 2:
            assert b in a.computed, f"shared uncomputed block {b}"
            writable = sum(
                1 for s, blocks in a.owned.items()
                if b in blocks
                and blocks.index(b) not in a.read_only.get(s, set()))
            assert writable <= 1, f"block {b} writable in {writable} tables"


def _admit(a: BlockAllocator, slot: int, prompt: np.ndarray, max_new=8,
           slack=10):
    """The scheduler's admission protocol against a bare allocator."""
    keys = prefix_block_keys(prompt, a.block_size)
    hit = a.match_prefix(keys)
    need = len(prompt) + max_new + slack
    if not a.can_allocate(a.blocks_needed(need) - len(hit), hit):
        return None
    a.allocate(slot, need, prefix=hit, keys=keys)
    return len(hit) * a.block_size


# ------------------------------------------------------------ deterministic
def test_register_match_and_computed_gating():
    a = BlockAllocator(num_blocks=16, block_size=4, max_batch=4, max_len=64)
    prompt = np.arange(10, dtype=np.int32)          # p-1 = 9 -> 2 full blocks
    keys = prefix_block_keys(prompt, 4)
    assert len(keys) == 2
    assert _admit(a, 0, prompt) == 0
    # registered but not computed: a concurrent identical prompt misses
    assert a.match_prefix(keys) == []
    a.mark_computed(0, 4)                           # prefill passed block 0
    assert a.match_prefix(keys) == [a.owned[0][0]]
    a.mark_computed(0, 9)                           # full prompt prefilled
    assert a.match_prefix(keys) == a.owned[0][:2]
    check_invariants(a)
    # second slot maps the prefix copy-free: refcount 2, shared read-only
    assert _admit(a, 1, prompt) == 8
    assert a.owned[1][:2] == a.owned[0][:2]
    assert all(a.ref[b] == 2 for b in a.owned[0][:2])
    check_invariants(a)
    # releases drop refs; the cached blocks park on the LRU, not free
    a.release(0)
    assert all(a.ref[b] == 1 for b in a.owned[1][:2])
    a.release(1)
    assert len(a.lru) == 2 and all(a.ref[b] == 0 for b in a.lru)
    check_invariants(a)
    # ...and still serve a later identical prompt
    assert _admit(a, 2, prompt) == 8
    check_invariants(a)


def test_lru_eviction_recycles_cold_blocks_only():
    a = BlockAllocator(num_blocks=8, block_size=4, max_batch=4, max_len=64)
    p1 = np.arange(5, dtype=np.int32)               # 1 full block
    p2 = 100 + np.arange(5, dtype=np.int32)
    _admit(a, 0, p1, max_new=2, slack=1)            # 2 blocks
    a.mark_computed(0, 4)
    _admit(a, 1, p2, max_new=2, slack=1)
    a.mark_computed(1, 4)
    a.release(0)
    a.release(1)                                    # LRU: [p1's, p2's]
    assert len(a.lru) == 2
    check_invariants(a)
    # a big allocation drains the free list then evicts the OLDEST entry
    free_before = len(a.free)
    a.allocate(2, (free_before + 1) * 4)
    assert len(a.lru) == 1
    check_invariants(a)
    # p1's registration was evicted; p2's prefix still hits
    assert a.match_prefix(prefix_block_keys(p1, 4)) == []
    assert len(a.match_prefix(prefix_block_keys(p2, 4))) == 1


def test_copy_on_write_detaches_shared_block():
    a = BlockAllocator(num_blocks=16, block_size=4, max_batch=4, max_len=64)
    prompt = np.arange(10, dtype=np.int32)
    _admit(a, 0, prompt)
    a.mark_computed(0, 9)
    _admit(a, 1, prompt)
    shared = a.owned[1][0]
    assert a.ref[shared] == 2
    pair = a.copy_on_write(1, 0)                    # slot 1 wants to write
    assert pair is not None and pair[0] == shared
    old, new = pair
    assert a.ref[old] == 1 and a.ref[new] == 1
    assert a.owned[1][0] == new and a.tables[1, 0] == new
    assert a.owned[0][0] == old                     # owner keeps the cached one
    check_invariants(a)
    # sole-owner cached block: detached from the index instead of copied
    assert a.copy_on_write(0, 0) is None
    assert old not in a.block_key
    check_invariants(a)
    # exclusive uncached block (slot 1's fresh tail): no-op
    assert a.copy_on_write(1, 2) is None
    check_invariants(a)


def test_can_allocate_excludes_lru_parked_prefix_blocks():
    """Regression: a matched prefix whose blocks sit ON the eviction LRU
    must not be double-counted as reclaimable capacity — the admission
    check has to report backpressure, not pass and then crash allocate()
    with an empty free list."""
    a = BlockAllocator(num_blocks=4, block_size=4, max_batch=3, max_len=32)
    prompt = np.arange(9, dtype=np.int32)           # p-1 = 8 -> 2 full blocks
    _admit(a, 0, prompt, max_new=2, slack=1)        # 3 blocks
    a.mark_computed(0, 8)
    a.release(0)                                    # 2 cached on LRU, 1 free
    a.allocate(1, 4)                                # drains the free list
    keys = prefix_block_keys(prompt, 4)
    hit = a.match_prefix(keys)
    assert len(hit) == 2 and all(b in a.lru for b in hit)
    # 3 blocks needed, 2 from the hit: ONE fresh block required, zero
    # reclaimable once the hit leaves the LRU -> must refuse
    assert not a.can_allocate(1, hit)
    assert a.can_allocate(0, hit)                   # the hit itself is fine
    check_invariants(a)
    # after the exclusive owner frees its block, admission goes through
    a.release(1)
    assert a.can_allocate(1, hit)
    assert _admit(a, 2, prompt, max_new=2, slack=1) == 8
    check_invariants(a)


def test_copy_on_write_exhausted_pool_raises_cleanly():
    a = BlockAllocator(num_blocks=4, block_size=4, max_batch=3, max_len=32)
    prompt = np.arange(9, dtype=np.int32)
    _admit(a, 0, prompt, max_new=2, slack=1)        # all 3 usable blocks
    a.mark_computed(0, 8)
    # share the prefix without fresh blocks: slot 1 maps only the hit
    hit = a.match_prefix(prefix_block_keys(prompt, 4))
    a.allocate(1, 8, prefix=hit)
    assert not a.can_allocate(1)
    with pytest.raises(RuntimeError, match="copy-on-write"):
        a.copy_on_write(1, 0)
    check_invariants(a)


def test_randomized_interleavings_hold_invariants():
    """Seeded-random version of the hypothesis property below — always
    runs, so the invariants keep local coverage without the optional dep."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        _run_interleaving(
            a=BlockAllocator(num_blocks=12, block_size=4, max_batch=3,
                             max_len=64),
            ops=rng.integers(0, 4, size=40).tolist(),
            picks=rng.integers(0, 100, size=40).tolist(),
            n_prompts=int(rng.integers(1, 4)))


def _run_interleaving(a: BlockAllocator, ops, picks, n_prompts):
    """Replay submit/prefill/complete/evict ops, checking invariants after
    every mutation. Prompts are drawn from a small pool so prefix sharing,
    computed gating and eviction all actually trigger."""
    prompts = [np.full(11, i, dtype=np.int32) for i in range(n_prompts)]
    live = {}                                 # slot -> (prompt, pf_cursor)
    for op, pick in zip(ops, picks):
        if op == 0:                           # submit into a free slot
            free = [s for s in range(3) if s not in live]
            if not free:
                continue
            prompt = prompts[pick % len(prompts)]
            pf = _admit(a, free[0], prompt, max_new=2, slack=1)
            if pf is not None:
                live[free[0]] = (prompt, pf)
        elif op == 1 and live:                # one prefill chunk
            slot = sorted(live)[pick % len(live)]
            prompt, pf = live[slot]
            pf = min(pf + 5, len(prompt) - 1)
            a.mark_computed(slot, pf)
            live[slot] = (prompt, pf)
        elif op == 2 and live:                # complete
            slot = sorted(live)[pick % len(live)]
            a.release(slot)
            del live[slot]
        elif op == 3:                         # allocation pressure / evict
            if a.can_allocate(2) and 2 not in live:
                unique = 200 + np.arange(9, dtype=np.int32)
                pf = _admit(a, 2, unique, max_new=2, slack=1)
                if pf is not None:
                    live[2] = (unique, pf)
        check_invariants(a)
    for slot in list(live):
        a.release(slot)
        check_invariants(a)
    assert a.blocks_in_use == 0


def test_hypothesis_interleavings_hold_invariants():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(ops=st.lists(st.integers(0, 3), min_size=1, max_size=60),
           picks=st.lists(st.integers(0, 99), min_size=60, max_size=60),
           n_prompts=st.integers(1, 4),
           block_size=st.sampled_from([2, 4, 8]))
    def run(ops, picks, n_prompts, block_size):
        _run_interleaving(
            a=BlockAllocator(num_blocks=12, block_size=block_size,
                             max_batch=3, max_len=64),
            ops=ops, picks=picks, n_prompts=n_prompts)

    run()


# -------------------------------------------------------------- engine level
@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _shared_prompts(rng, n, sys_len=33, tail=5):
    sys_p = rng.integers(0, 512, size=sys_len).astype(np.int32)
    return [np.concatenate([sys_p,
                            rng.integers(0, 512, size=tail).astype(np.int32)])
            for _ in range(n)]


@pytest.mark.parametrize("temps", [[0.0] * 5, [0.0, 0.8, 0.0, 0.7, 0.8]],
                         ids=["greedy", "mixed-sampled"])
def test_cache_hit_completions_identical_to_cold(models, temps):
    """Cache-hit completions must be token-identical to cold ones: the
    same requests with the prefix cache off (cold) and on (warm). Greedy
    rows are deterministic; sampled rows are seeded per (seed, rid), so
    their trajectories must also be invariant to the KV source."""
    from repro.serving.engine import Engine
    tc, tp, dc, dp = models
    rng = np.random.default_rng(7)
    prompts = _shared_prompts(rng, 5)
    results = {}
    for name, cache in [("cold", False), ("warm", True)]:
        eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                     max_len=256, kv_block_size=16,
                     prefix_cache=cache, seed=0)
        rids = {eng.submit(p, 12, temperature=t): i
                for i, (p, t) in enumerate(zip(prompts, temps))}
        results[name] = {rids[c.rid]: c.tokens for c in eng.run()}
        if name == "warm":
            assert eng.prefix_hit_rate() > 0.5
            assert eng.alloc.blocks_in_use == 0
    for i in range(len(prompts)):
        assert np.array_equal(results["cold"][i], results["warm"][i])


def test_live_sharing_refcounts_and_block_savings(models):
    """Two later same-prefix requests map the finished request's cached
    blocks copy-free WHILE LIVE (refcount 2 each) and allocate strictly
    fewer fresh blocks than a cold admission would."""
    from repro.serving.engine import Engine
    tc, tp, dc, dp = models
    rng = np.random.default_rng(8)
    prompts = _shared_prompts(rng, 3)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=16, prefix_cache=True)
    eng.submit(prompts[0], 8)
    eng.run()
    free_before = len(eng.alloc.free) + len(eng.alloc.lru)
    eng.submit(prompts[1], 8)
    eng.submit(prompts[2], 8)
    eng.sched.admit()
    shared = [b for b in eng.alloc.owned[0] if eng.alloc.ref[b] == 2]
    assert len(shared) == 2                       # both full prompt blocks
    assert shared == eng.alloc.owned[1][:2]
    check_invariants(eng.alloc)
    # both admissions drew only their tails from the free pool
    taken = free_before - len(eng.alloc.free) - len(eng.alloc.lru)
    cold_need = 2 * eng.alloc.blocks_needed(
        len(prompts[1]) + 8 + eng.dec.window_slack)
    assert taken < cold_need
    comps = eng.run()
    assert len(comps) == 3 and eng.alloc.blocks_in_use == 0


def test_prefix_keys_are_content_exact():
    p1 = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    p2 = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    p3 = np.asarray([1, 2, 3, 9, 5, 6, 7, 8, 9], np.int32)
    assert prefix_block_keys(p1, 4) == prefix_block_keys(p2, 4)
    k1, k3 = prefix_block_keys(p1, 4), prefix_block_keys(p3, 4)
    assert k1[0] != k3[0] and k1[1] != k3[1]      # chained: divergence sticks
    # only FULL blocks inside prompt[:-1] are keyed
    assert len(prefix_block_keys(np.arange(9, dtype=np.int32), 4)) == 2
    assert len(prefix_block_keys(np.arange(8, dtype=np.int32), 4)) == 1
    assert len(prefix_block_keys(np.arange(4, dtype=np.int32), 4)) == 0


def test_default_num_blocks_unchanged():
    assert kv_pool.default_num_blocks(2, 128, 32) == 2 * 4 + 1


# ------------------------------------------------------------ quantized pools
def test_prefix_keys_salted_by_kv_dtype_never_alias():
    """A cached block's payload is the dtype-specific encoding (quantized
    values + scales vs full precision), so the same token prefix under
    different kv_dtypes must produce disjoint key sets — and the default
    salt is byte-identical to the historical unsalted keys' dtype."""
    p = np.arange(1, 200, dtype=np.int32)
    per_dtype = {name: prefix_block_keys(p, 64, kv_dtype=name)
                 for name in ("bf16", "fp32", "int8", "fp8")}
    names = list(per_dtype)
    for i, a in enumerate(names):
        assert len(per_dtype[a]) == 3             # (199 - 1) // 64 full blocks
        for b in names[i + 1:]:
            assert not set(per_dtype[a]) & set(per_dtype[b])
    assert prefix_block_keys(p, 64) == per_dtype["bf16"]


def test_warm_cache_identical_to_cold_under_int8(models):
    """Warm-vs-cold token identity holds with a quantized pool: a cache
    hit serves the EXACT int8 blocks (values + scales) the registering
    request appended, so greedy and seeded-sampled trajectories are
    invariant to the KV source under int8 too."""
    from repro.serving.engine import Engine
    tc, tp, dc, dp = models
    rng = np.random.default_rng(11)
    prompts = _shared_prompts(rng, 5)
    temps = [0.0, 0.8, 0.0, 0.7, 0.8]
    results = {}
    for name, cache in [("cold", False), ("warm", True)]:
        eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                     max_len=256, kv_block_size=16,
                     prefix_cache=cache, kv_dtype="int8", seed=0)
        rids = {eng.submit(p, 12, temperature=t): i
                for i, (p, t) in enumerate(zip(prompts, temps))}
        results[name] = {rids[c.rid]: c.tokens for c in eng.run()}
        check_invariants(eng.alloc)
        if name == "warm":
            assert eng.prefix_hit_rate() > 0.5
            assert eng.alloc.blocks_in_use == 0
    for i in range(len(prompts)):
        assert np.array_equal(results["cold"][i], results["warm"][i])


def test_live_sharing_and_cow_invariants_under_int8(models):
    """Refcounted live sharing + COW semantics are dtype-agnostic: the
    allocator tracks BLOCK INDICES, and the executor's copy_block copies
    every pool leaf (scales included). Run the live-sharing scenario on an
    int8 engine and hold the I1-I5 invariants throughout."""
    from repro.serving.engine import Engine
    tc, tp, dc, dp = models
    rng = np.random.default_rng(12)
    prompts = _shared_prompts(rng, 3)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=16, prefix_cache=True,
                 kv_dtype="int8")
    eng.submit(prompts[0], 8)
    eng.run()
    eng.submit(prompts[1], 8)
    eng.submit(prompts[2], 8)
    eng.sched.admit()
    shared = [b for b in eng.alloc.owned[0] if eng.alloc.ref[b] == 2]
    assert len(shared) == 2                       # both full prompt blocks
    assert shared == eng.alloc.owned[1][:2]
    check_invariants(eng.alloc)
    # exercise COW on a live shared block: the detached copy gets its own
    # refcount-1 block whose VALUES AND SCALES are byte-identical to the
    # donor's (copy_block is a generic tree.map over every pool leaf)
    pair = eng.alloc.copy_on_write(0, 0)
    assert pair is not None                       # shared -> must remap
    src, dst = pair
    assert src == shared[0] and dst != src
    assert eng.alloc.ref[dst] == 1 and eng.alloc.ref[src] == 1
    eng.ex.copy_block(src, dst)
    for c, scanned in ([(c, False) for c in eng.ex.state.tcache["prefix"]]
                       + [(c, True) for c in eng.ex.state.tcache["scan"]]):
        if "k" not in c and "ckv" not in c:
            continue                              # SSM/cross: not paged KV
        for leaf in c.values():
            blk = (lambda i, x=leaf: x[:, i]) if scanned \
                else (lambda i, x=leaf: x[i])
            np.testing.assert_array_equal(np.asarray(blk(src)),
                                          np.asarray(blk(dst)))
    check_invariants(eng.alloc)
    comps = eng.run()                             # cumulative completions
    assert len(comps) == 3 and eng.alloc.blocks_in_use == 0
