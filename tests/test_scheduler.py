"""Layered scheduler/executor stack (DESIGN.md §8): chunked prefill fused
into the decode step, FIFO-fair skip-ahead admission, prefill budgeting,
EOS truncation, and per-request latency accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.spec_decode import SpecDecoder
from repro.models import init_params
from repro.serving.engine import Engine

from decode_refs import assert_greedy_up_to_tie, greedy_reference


@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _prompts(rng, n, lo=4, hi=14, vocab=512):
    return [rng.integers(0, vocab, size=int(t)).astype(np.int32)
            for t in rng.integers(lo, hi, size=n)]


# ------------------------------------------------------------ chunked prefill
def test_admission_never_runs_standalone_prefill(models):
    """Acceptance criterion: with >= 2 decoding rows live, admitting a new
    request never runs a standalone prefill forward — target_forwards
    counts STEPS only, prefill happens as chunks inside those steps, and
    the completions still match per-request AR references."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(20)
    prompts = _prompts(rng, 6, lo=8, hi=20)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=3, max_len=256)
    rids = {eng.submit(p, 10): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    # the structural assert: one target forward per step, nothing else
    assert eng.stats["target_forwards"] == eng.stats["steps"]
    assert eng.stats["prefill_chunks"] > 0
    assert eng.stats["prefill_tokens"] == sum(len(p) - 1 for p in prompts)
    # 6 requests through 3 slots: admissions 4..6 happened while >= 2 rows
    # were decoding, i.e. mixed prefill+decode steps ran
    for c in comps:
        i = rids[c.rid]
        dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
        ref = np.asarray(dec.generate_ar(
            jnp.asarray(prompts[i])[None], 10)[0][0])
        assert np.array_equal(ref, c.tokens)


def test_mixed_phase_steps_paged_matches_contiguous(models):
    """Acceptance criterion: mixed prefill+decode steps over the KV pool
    (7 ragged requests through 2 slots: every admission prefills beside a
    decoding row) produce AR's greedy completions. Request 6 meets a
    bf16 near-tie at its 11th new token: the float32 forward ranks the
    engine's token first by 0.014 over AR's (a tie in bf16), so that row
    is held to the float32 greedy choice up to the tie."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(21)
    prompts = _prompts(rng, 7, lo=6, hi=24)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                 max_len=256, kv_block_size=32)
    rids = {eng.submit(p, 11): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert eng.stats["target_forwards"] == eng.stats["steps"]
    assert len(comps) == len(prompts)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256, kv_block_size=32)
    for c in comps:
        i = rids[c.rid]
        if i == 6:
            assert_greedy_up_to_tie(dec, prompts[i], c.tokens, 11)
        else:
            assert np.array_equal(greedy_reference(dec, prompts[i], 11),
                                  c.tokens)


def test_ar_mode_chunked_prefill_matches_reference(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(22)
    prompts = _prompts(rng, 5, lo=6, hi=30)
    eng = Engine(tp, tc, dp, dc, mode="ar", max_batch=2, max_len=256,
                 prefill_chunk=8)
    rids = {eng.submit(p, 9): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert eng.stats["target_forwards"] == eng.stats["steps"]
    for c in comps:
        i = rids[c.rid]
        dec = SpecDecoder(tp, tc, None, None, k=1, max_len=256)
        ref = np.asarray(dec.generate_ar(
            jnp.asarray(prompts[i])[None], 9)[0][0])
        assert np.array_equal(ref, c.tokens)


def test_tree_engine_chunked_prefill(models):
    """Chunked prefill through the tree step: causal chunk masks ride the
    tree-attention kernels; completions still match the AR reference."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(23)
    prompts = _prompts(rng, 4, lo=8, hi=20)
    eng = Engine(tp, tc, tp, tc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32, tree=(2, 2, 2, 1))
    rids = {eng.submit(p, 10): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert eng.stats["target_forwards"] == eng.stats["steps"]
    assert eng.stats["prefill_chunks"] > 0
    for c in comps:
        i = rids[c.rid]
        dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
        ref = np.asarray(dec.generate_ar(
            jnp.asarray(prompts[i])[None], 10)[0][0])
        assert np.array_equal(ref, c.tokens)


def test_tree_chunked_prefill_near_max_len(models):
    """A chain-pinned row admitted at the max_len feasibility bound: the
    prefill cursor runs close to the buffer end, where slicing the chunk at
    the bank-wide window width would clamp and silently shift the prompt —
    the chunk must slice at the (narrower) chunk width instead."""
    tc, tp, dc, dp = models
    from repro.core.spec_decode import TemplateBank
    rng = np.random.default_rng(31)
    bank = TemplateBank.default(4)                   # widest window 23 slots
    max_len, max_new = 128, 6
    dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=max_len, tree=bank)
    p_len = max_len - max_new - dec.row_slack(0)     # chain slack, exactly
    prompt = rng.integers(0, 512, size=p_len).astype(np.int32)
    eng = Engine(tp, tc, tp, tc, mode="pard", k=4, max_batch=1,
                 max_len=max_len, kv_block_size=32,
                 tree=bank)
    eng.submit(prompt, max_new, tree_idx=0)
    out = eng.run()[0]
    ref_dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=512)
    ref = np.asarray(ref_dec.generate_ar(
        jnp.asarray(prompt)[None], max_new)[0][0])
    assert np.array_equal(ref, out.tokens)


# ---------------------------------------------------------------- admission
def test_head_of_line_skip_ahead(models):
    """A pool-oversized request at the queue head must not starve smaller
    requests behind it: they admit (within the bounded scan window) while
    the big one waits for blocks, and everything still completes."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(24)
    small = [rng.integers(0, 512, size=8).astype(np.int32) for _ in range(3)]
    big = rng.integers(0, 512, size=130).astype(np.int32)
    # slack = max(2K, K+1) + 2 = 10; small: 8+8+10=26 -> 1 block of 32;
    # big: 130+8+10=148 -> 5 blocks. Pool: 5 usable -> big needs ALL of it
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32, kv_num_blocks=6)
    r_small0 = eng.submit(small[0], 8)
    r_big = eng.submit(big, 8)
    r_next = eng.submit(small[1], 8)
    r_last = eng.submit(small[2], 8)
    comps = eng.run()
    assert len(comps) == 4
    order = [c.rid for c in comps]
    # small[1], queued BEHIND the infeasible big, overtook it instead of
    # starving (small[2] then legitimately waits: the admitted big holds
    # the whole pool, and it completes afterwards — nothing deadlocks)
    assert order.index(r_small0) < order.index(r_big)
    assert order.index(r_next) < order.index(r_big)
    assert r_last in order
    big_tokens = next(c for c in comps if c.rid == r_big)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    ref = np.asarray(dec.generate_ar(jnp.asarray(big)[None], 8)[0][0])
    assert np.array_equal(ref, big_tokens.tokens)


def test_admit_window_bounds_overtaking(models):
    """Requests beyond ``admit_window`` may never jump the queue: with a
    window of 1 the blocked head pins everything behind it (the old strict
    FIFO), so the oversized head admits FIRST once blocks free up."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(25)
    big = rng.integers(0, 512, size=130).astype(np.int32)
    small = rng.integers(0, 512, size=8).astype(np.int32)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32, kv_num_blocks=6,
                 admit_window=1)
    r_first = eng.submit(small, 8)
    r_big = eng.submit(big, 8)
    r_last = eng.submit(small, 8)
    comps = eng.run()
    order = [c.rid for c in comps]
    assert order.index(r_first) < order.index(r_big) < order.index(r_last)


def test_oversized_request_still_fails_loudly(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(26)
    p = rng.integers(0, 512, size=16).astype(np.int32)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=512,
                 kv_block_size=32, kv_num_blocks=2)
    eng.submit(p, 24)                            # needs 2 blocks; pool has 1
    with pytest.raises(RuntimeError, match="KV blocks"):
        eng.run()


def test_prefill_budget_caps_concurrent_lanes(models):
    """``prefill_budget`` tokens/step caps CONCURRENT prefilling rows at
    budget // chunk lanes — observed across every scheduler tick."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(27)
    prompts = _prompts(rng, 6, lo=20, hi=40)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=4, max_len=256,
                 prefill_budget=5)               # chunk=K+1=5 -> 1 lane
    assert eng.sched.prefill_lanes == 1
    seen = []
    orig = eng.ex.dispatch

    def spy(*args, **kw):
        seen.append(eng.sched.prefilling_count())
        return orig(*args, **kw)

    eng.ex.dispatch = spy
    for p in prompts:
        eng.submit(p, 8)
    comps = eng.run()
    assert len(comps) == len(prompts)
    assert max(seen) == 1                        # never two prefill lanes
    # control: without a budget the same workload overlaps prefills
    eng2 = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=4, max_len=256)
    seen2 = []
    orig2 = eng2.ex.dispatch

    def spy2(*args, **kw):
        seen2.append(eng2.sched.prefilling_count())
        return orig2(*args, **kw)

    eng2.ex.dispatch = spy2
    for p in prompts:
        eng2.submit(p, 8)
    eng2.run()
    assert max(seen2) > 1


# ------------------------------------------------------------- EOS + latency
def test_eos_truncates_mid_window_commits(models):
    """Regression (ISSUE 5 satellite): tokens speculatively committed AFTER
    an EOS inside the same verify window must not leak into the completion
    or its ``generated`` count."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(28)
    p = rng.integers(0, 512, size=6).astype(np.int32)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    full = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 16)[0][0])
    eos = int(full[len(p) + 5])                  # mid-window position
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=1, max_len=256,
                 eos_id=eos, kv_block_size=32)
    eng.submit(p, 16)
    out = eng.run()[0]
    gen = out.tokens[len(p):].tolist()
    assert eos in gen
    # the completion ends AT the eos — nothing committed past it survives
    assert gen.index(eos) == len(gen) - 1
    assert out.generated == len(gen)
    assert np.array_equal(out.tokens, full[:len(out.tokens)])


def test_latency_accounting(models):
    """Every completion records queue wait, TTFT and per-token percentile
    latencies; requests admitted behind a full batch see a positive queue
    wait, and the summary aggregates sanely."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(29)
    prompts = _prompts(rng, 5, lo=8, hi=16)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256)
    rids = {eng.submit(p, 10): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    for c in comps:
        assert c.queue_wait >= 0.0
        assert c.ttft > c.queue_wait            # first token needs steps
        assert c.wall_done - c.wall_submitted >= c.ttft
        assert 0.0 < c.tok_p50 <= c.tok_p95
    # later requests waited for a slot behind the first two
    by_req = {rids[c.rid]: c for c in comps}
    assert by_req[4].queue_wait > by_req[0].queue_wait
    s = eng.latency_summary()
    assert s["requests"] == len(prompts)
    assert 0 < s["ttft_p50_ms"] <= s["ttft_p95_ms"]
    assert 0 < s["tok_p50_ms"]


def test_prefix_hit_shortens_ttft_steps(models):
    """A full-prefix cache hit skips every prefill chunk: the request's
    first token arrives after strictly fewer engine steps."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(30)
    prompt = rng.integers(0, 512, size=65).astype(np.int32)  # 64 = 4 blocks

    def steps_to_first(eng):
        eng.submit(prompt, 6)
        before = eng.stats["steps"]
        eng.run()
        c = eng.completions[-1]
        # prefill chunks ran as steps before the first commit
        return eng.stats["steps"] - before, c

    cold = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=1, max_len=256,
                  kv_block_size=16, prefix_cache=True)
    n_cold, c_cold = steps_to_first(cold)
    n_warm, c_warm = steps_to_first(cold)        # same engine: cache is hot
    assert cold.prefix_hit_rate() > 0
    assert n_warm < n_cold
    assert np.array_equal(c_cold.tokens, c_warm.tokens)
