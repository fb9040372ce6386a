"""Data-parallel engine replicas behind one scheduler (DESIGN.md §12).

Host-only portion (tier-1): the shared PrefixIndex registry, and the
scheduler's prefix-affinity-then-least-loaded ``_route_order`` exercised
directly against real BlockAllocators (no devices, no models).

Multi-device portion (CI dp-gate: REPRO_HOST_DEVICES=4): dp=2 engines are
token-set-identical to dp=1 for the same request set over bf16 and int8
pools,
same-prefix requests route to the replica owning the cached blocks, and a
full owner replica overflows to the other replica instead of stalling.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import kv_pool, scheduler as sched_mod
from repro.serving.config import EngineConfig, SamplingParams
from repro.serving.engine import Engine

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 host devices (REPRO_HOST_DEVICES=4)")

BS = 16     # block size used by every host-only allocator below


# ------------------------------------------------------------ PrefixIndex
def _alloc(index=None, replica=0, num_blocks=32):
    return kv_pool.BlockAllocator(num_blocks, BS, max_batch=4, max_len=256,
                                  replica=replica, prefix_index=index)


def _seed_prefix(alloc, slot, prompt):
    """Admit ``prompt`` into ``alloc`` the way the scheduler does and mark
    its prompt blocks computed (matchable)."""
    keys = kv_pool.prefix_block_keys(prompt, BS)
    alloc.allocate(slot, len(prompt), keys=keys)
    alloc.mark_computed(slot, len(prompt))
    return keys


def test_prefix_index_registers_each_replica_once():
    idx = kv_pool.PrefixIndex()
    a0 = _alloc(idx, replica=0)
    assert idx.allocators == {0: a0}
    with pytest.raises(ValueError, match="already registered"):
        _alloc(idx, replica=0)
    a1 = _alloc(idx, replica=1)
    assert idx.allocators == {0: a0, 1: a1}


def test_prefix_index_best_replica_longest_hit_ties_low():
    idx = kv_pool.PrefixIndex()
    a0, a1 = _alloc(idx, 0), _alloc(idx, 1)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, size=3 * BS + 4).astype(np.int32)
    keys = kv_pool.prefix_block_keys(prompt, BS)
    assert len(keys) == 3
    # replica 1 holds the full 3-block prefix, replica 0 only 1 block
    _seed_prefix(a1, 0, prompt)
    _seed_prefix(a0, 0, prompt[:BS + 2])
    best_r, blocks = idx.best_replica(keys)
    assert best_r == 1 and len(blocks) == 3
    assert {r: len(m) for r, m in idx.match(keys).items()} == {0: 1, 1: 3}
    # equal hit lengths tie to the lowest replica id (deterministic)
    _seed_prefix(a0, 1, prompt)
    best_r, blocks = idx.best_replica(keys)
    assert best_r == 0 and len(blocks) == 3
    # no replica holds anything for a foreign prompt
    other = rng.integers(0, 512, size=2 * BS).astype(np.int32)
    assert idx.best_replica(kv_pool.prefix_block_keys(other, BS)) \
        == (None, [])


def test_prefix_index_requires_computed_blocks():
    """An allocated-but-not-yet-prefilled block must not attract routing
    (I5: match_prefix only returns computed blocks)."""
    idx = kv_pool.PrefixIndex()
    a0 = _alloc(idx, 0)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, size=2 * BS).astype(np.int32)
    keys = kv_pool.prefix_block_keys(prompt, BS)
    a0.allocate(0, len(prompt), keys=keys)       # no mark_computed
    assert idx.best_replica(keys) == (None, [])
    a0.mark_computed(0, len(prompt))
    assert idx.best_replica(keys)[0] == 0


# ------------------------------------------------- _route_order (host-only)
class _FakeDec:
    """The slice of SpecDecoder the Scheduler reads at construction/submit
    time; routing itself never touches the decoder."""
    chunk_width = 8
    window_slack = 4
    min_row_slack = 4


class _FakeEx:
    kv_dtype = "bf16"


def _routing_sched(dp=2, prefix_cache=True, paged=True):
    idx = kv_pool.PrefixIndex() if paged else None
    allocs = [_alloc(idx, r) for r in range(dp)] if paged else [None] * dp
    return sched_mod.Scheduler(
        [_FakeDec()] * dp, [_FakeEx()] * dp, allocs, mode="pard",
        max_batch=4, max_len=256, temperature=0.0, eos_id=None,
        bank=None, ctrl=None, prefix_cache=prefix_cache, admit_window=4,
        prefill_budget=None, tree_reselect_every=4, prefix_index=idx)


def _req(prompt):
    return sched_mod.Request(0, np.asarray(prompt, np.int32),
                             SamplingParams(max_new=8))


def test_route_order_miss_goes_least_loaded():
    sched = _routing_sched()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, size=2 * BS).astype(np.int32)
    # ties break to the lowest replica id
    assert [(r.rep, h) for r, h in sched._route_order(_req(prompt))] \
        == [(0, 0), (1, 0)]
    # load replica 0 -> the emptier replica 1 now goes first
    sched.replicas[0].slots[0] = _req(prompt)
    sched.replicas[0]._occ_cache = None
    assert [(r.rep, h) for r, h in sched._route_order(_req(prompt))] \
        == [(1, 0), (0, 0)]


def test_route_order_hit_routes_to_owner_even_when_loaded():
    sched = _routing_sched()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 512, size=3 * BS + 2).astype(np.int32)
    # replica 1 owns the prefix AND is the more loaded replica: affinity
    # must still place it first (the hit is served copy-free there)
    _seed_prefix(sched.replicas[1].alloc, 0, prompt)
    sched.replicas[1].slots[0] = _req(prompt)
    sched.replicas[1]._occ_cache = None
    order = sched._route_order(_req(prompt))
    assert [(r.rep, h) for r, h in order] == [(1, 3), (0, 0)]
    # a different prompt ignores the cached blocks: pure least-loaded
    other = rng.integers(0, 512, size=2 * BS).astype(np.int32)
    assert [(r.rep, h) for r, h in sched._route_order(_req(other))] \
        == [(0, 0), (1, 0)]


def test_route_order_trivial_without_prefix_cache():
    for sched in (_routing_sched(prefix_cache=False),
                  _routing_sched(paged=False, prefix_cache=False),
                  _routing_sched(dp=1)):
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, 512, size=2 * BS).astype(np.int32)
        order = sched._route_order(_req(prompt))
        assert [h for _, h in order] == [0] * sched.dp
        assert [r.rep for r, _ in order] == sorted(
            range(sched.dp),
            key=lambda i: (sched.replicas[i].occupancy(), i))


# ------------------------------------------------ end-to-end (multi-device)
@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _mixed_submit(eng, reqs, max_new=24):
    rids = {}
    for i, r in enumerate(reqs):
        rids[eng.submit(r, params=SamplingParams(
            max_new=max_new,
            temperature=0.0 if i % 2 == 0 else 0.8,
            seed=None if i % 2 == 0 else 50 + i))] = i
    return rids


def _run_tokens(models, reqs, **cfg_kw):
    tc, tp, dc, dp = models
    eng = Engine(tp, tc, dp, dc, config=EngineConfig(
        mode="pard", k=4, max_batch=2, max_len=256, seed=7, **cfg_kw))
    rids = _mixed_submit(eng, reqs)
    out = {rids[c.rid]: c.tokens for c in eng.run()}
    return out, eng


@needs2
@pytest.mark.parametrize("kv_dtype,prefix_cache",
                         [("bf16", True), ("int8", False)])
def test_dp2_token_set_identical_to_dp1(models, kv_dtype, prefix_cache):
    """The acceptance gate: dp=2 commits exactly the token set of dp=1
    for the same mixed greedy + pinned-seed sampled request set, with
    prefix-affinity routing over bf16 pools and least-loaded routing over
    int8 ones (routing must never leak into the tokens)."""
    rng = np.random.default_rng(5)
    sys_p = rng.integers(0, 512, size=64).astype(np.int32)
    reqs = [np.concatenate([sys_p,
                            rng.integers(0, 512, size=6).astype(np.int32)])
            for _ in range(6)]
    kw = dict(kv_dtype=kv_dtype, kv_block_size=64, pipelined=True,
              prefix_cache=prefix_cache)
    base, _ = _run_tokens(models, reqs, dp=1, **kw)
    out, eng = _run_tokens(models, reqs, dp=2, **kw)
    assert set(base) == set(out)
    for i in base:
        assert np.array_equal(base[i], out[i]), f"request {i} diverged"
    assert len(eng.stats["replica_steps"]) == 2
    assert all(s > 0 for s in eng.stats["replica_steps"])


@needs2
def test_dp2_same_prefix_requests_route_to_owner(models):
    """Warm same-prefix requests land on the replica already holding the
    cached blocks: the scheduler counts them as affinity-routed and the
    warm pass serves the prompt blocks from the cache."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(6)
    sys_p = rng.integers(0, 512, size=64).astype(np.int32)
    reqs = [np.concatenate([sys_p,
                            rng.integers(0, 512, size=6).astype(np.int32)])
            for _ in range(4)]
    eng = Engine(tp, tc, dp, dc, config=EngineConfig(
        mode="pard", k=4, max_batch=2, max_len=256, seed=7, dp=2,
        kv_block_size=64, prefix_cache=True))
    _mixed_submit(eng, reqs)
    eng.run()                                    # cold: seeds one replica
    eng.stats.update(affinity_routed=0, prefix_lookup_blocks=0,
                     prefix_hit_blocks=0)
    rids = _mixed_submit(eng, reqs)
    out = {rids[c.rid]: c for c in eng.run()[-len(reqs):]}
    assert len(out) == len(reqs)
    # every warm request found its owner (and its cached prompt block)
    assert eng.stats["affinity_routed"] == len(reqs)
    assert eng.prefix_hit_rate() == 1.0


@needs2
def test_dp2_full_owner_overflows_not_stalls(models):
    """More same-prefix requests than the owning replica has slots: the
    overflow admits on the OTHER replica immediately (fall-through) rather
    than queueing behind the full owner, and everything completes."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(8)
    sys_p = rng.integers(0, 512, size=64).astype(np.int32)
    reqs = [np.concatenate([sys_p,
                            rng.integers(0, 512, size=6).astype(np.int32)])
            for _ in range(6)]
    eng = Engine(tp, tc, dp, dc, config=EngineConfig(
        mode="pard", k=4, max_batch=2, max_len=256, seed=7, dp=2,
        kv_block_size=64, prefix_cache=True))
    _mixed_submit(eng, reqs)
    eng.run()                                    # warm one replica's cache
    _mixed_submit(eng, reqs)                     # 6 warm same-prefix reqs
    comps = eng.run()
    assert len(comps) == 2 * len(reqs)
    # the owner (2 slots) cannot hold all 6: some admissions must have
    # overflowed to the other replica, and both replicas must have stepped
    assert all(s > 0 for s in eng.stats["replica_steps"])
