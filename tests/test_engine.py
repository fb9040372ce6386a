"""Batched serving engine: continuous batching, slot reuse, mode equality,
EOS handling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.spec_decode import SpecDecoder
from repro.models import init_params
from repro.serving import kv_pool
from repro.serving.engine import Engine


@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _prompts(rng, n, vocab=512):
    return [rng.integers(0, vocab, size=int(n_tok)).astype(np.int32)
            for n_tok in rng.integers(4, 14, size=n)]


def test_single_request_matches_specdecoder(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(1)
    p = rng.integers(0, 512, size=7).astype(np.int32)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    ref = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 16)[0][0])
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=1, max_len=256)
    eng.submit(p, 16)
    out = eng.run()[0]
    assert np.array_equal(ref, out.tokens)


def test_modes_agree_batched(models):
    """ar / vsd / pard must produce identical tokens per request under the
    same batching (lossless property at engine level)."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, 5)
    results = {}
    for mode in ("ar", "vsd", "pard"):
        eng = Engine(tp, tc, dp, dc, mode=mode, k=4, max_batch=2, max_len=256)
        rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
        comps = eng.run()
        assert len(comps) == len(prompts)
        results[mode] = {rids[c.rid]: c.tokens for c in comps}
    for i in range(len(prompts)):
        assert np.array_equal(results["ar"][i], results["vsd"][i])
        assert np.array_equal(results["ar"][i], results["pard"][i])


def test_continuous_batching_slot_reuse(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, 7)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256)
    for p in prompts:
        eng.submit(p, 10)
    comps = eng.run()
    assert len(comps) == 7
    for c in comps:
        assert c.generated == 10


def test_eos_stops_early(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(4)
    p = rng.integers(0, 512, size=6).astype(np.int32)
    # find what the model actually generates, then use its 3rd token as EOS
    eng0 = Engine(tp, tc, dp, dc, mode="ar", k=4, max_batch=1, max_len=256)
    eng0.submit(p, 12)
    full = eng0.run()[0].tokens
    eos = int(full[len(p) + 2])
    eng = Engine(tp, tc, dp, dc, mode="ar", k=4, max_batch=1, max_len=256,
                 eos_id=eos)
    eng.submit(p, 12)
    out = eng.run()[0]
    assert out.generated <= 12
    assert eos in out.tokens[len(p):].tolist()


def test_paged_bytes_scale_with_fill(models):
    """Short-prompt workload at max_len=1024: the engine's peak KV bytes in
    use must stay under 50% of its worst-case pool (``default_num_blocks``:
    every slot filled to max_len — acceptance criterion, HBM tracks actual
    fill, not max_batch x max_len), with every completion still equal to
    its own greedy AR reference."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, 5)
    paged = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                   max_len=1024, kv_block_size=64)
    worst = kv_pool.default_num_blocks(2, 1024, 64)
    assert paged.alloc.num_blocks == worst
    rids = {paged.submit(p, 16): p for p in prompts}
    out = {c.rid: c.tokens for c in paged.run()}
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=1024, kv_block_size=64)
    for rid, p in rids.items():
        ref = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 16)[0][0])
        assert np.array_equal(ref, out[rid])
    assert paged.peak_kv_bytes_in_use > 0
    assert paged.peak_kv_bytes_in_use < 0.5 * paged.kv_capacity_bytes()
    assert paged.kv_capacity_bytes() == worst * paged.ex.kv_per_block
    assert paged.kv_bytes_in_use() == 0          # everything released


def test_paged_ragged_arrival_order(models):
    """More ragged requests than slots, arriving in one burst: every
    completion must match its own single-request greedy reference (no
    cross-request KV leakage through the shared pool)."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, 5)
    max_news = [9, 14, 7, 12, 10]
    refs = {}
    for i, (p, mn) in enumerate(zip(prompts, max_news)):
        dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
        refs[i] = np.asarray(dec.generate_ar(jnp.asarray(p)[None], mn)[0][0])
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32)
    rids = {eng.submit(p, mn): i for i, (p, mn)
            in enumerate(zip(prompts, max_news))}
    comps = eng.run()
    assert len(comps) == len(prompts)
    for c in comps:
        assert np.array_equal(refs[rids[c.rid]], c.tokens)


def test_paged_eos_mid_verify_window(models):
    """EOS produced inside a speculative verify window (mode=pard commits
    up to K+1 tokens per step) must stop the request — and the tokens up to
    and including EOS must still match the AR reference."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(9)
    p = rng.integers(0, 512, size=6).astype(np.int32)
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    full = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 16)[0][0])
    eos = int(full[len(p) + 5])                  # mid-window position
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=1, max_len=256,
                 eos_id=eos, kv_block_size=32)
    eng.submit(p, 16)
    out = eng.run()[0]
    assert out.generated <= 16
    gen = out.tokens[len(p):]
    assert eos in gen.tolist()
    cut = gen.tolist().index(eos) + 1
    assert np.array_equal(out.tokens[:len(p) + cut], full[:len(p) + cut])


def test_paged_slot_reuse_reallocates_blocks(models):
    """Continuous batching through a deliberately tight pool: freed slots'
    blocks MUST be handed to later requests (the pool is too small to serve
    them otherwise), old KV is never attended (outputs match per-request
    references), and admission backpressure never deadlocks."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(10)
    prompts = _prompts(rng, 6)
    need_blocks = max(len(p) + 10 + 2 * 4 + 2 for p in prompts) // 32 + 1
    # room for ~2 concurrent requests; 6 requests => reuse is forced
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32,
                 kv_num_blocks=1 + 2 * need_blocks)
    allocs = []

    def spy(slot, n, _orig=eng.alloc.allocate):
        _orig(slot, n)
        allocs.append(list(eng.alloc.owned[slot]))

    eng.alloc.allocate = spy
    rids = {eng.submit(p, 10): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    seen = [b for al in allocs for b in al]
    assert len(seen) > len(set(seen))            # some block served >1 request
    for c in comps:
        i = rids[c.rid]
        dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
        ref = np.asarray(dec.generate_ar(
            jnp.asarray(prompts[i])[None], 10)[0][0])
        assert np.array_equal(ref, c.tokens)
    assert eng.alloc.blocks_in_use == 0


def test_submit_rejects_request_exceeding_max_len(models):
    """Oversized requests must fail at submit() with a real error — past
    admission they would outgrow their cache rows/blocks and silently
    attend garbage."""
    tc, tp, dc, dp = models
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=1, max_len=64)
    with pytest.raises(ValueError, match="cache positions"):
        eng.submit(np.arange(40, dtype=np.int32) % 512, 32)  # 40+32+10 > 64
    with pytest.raises(ValueError):
        eng.submit(np.asarray([1], np.int32), 4)             # prompt < 2


def test_paged_oversized_request_fails_loudly(models):
    """A request that cannot fit the pool even when it is empty must raise
    instead of spinning on admission backpressure forever."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(11)
    p = rng.integers(0, 512, size=16).astype(np.int32)
    eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2, max_len=512,
                 kv_block_size=32, kv_num_blocks=2)
    eng.submit(p, 24)                            # needs 2 blocks; pool has 1
    with pytest.raises(RuntimeError, match="KV blocks"):
        eng.run()


def test_hybrid_engine(models):
    jc = get_config("jamba-1.5-large-398b-smoke")
    jp = init_params(jax.random.PRNGKey(4), jc)
    rng = np.random.default_rng(5)
    p = rng.integers(0, jc.vocab_size, size=7).astype(np.int32)
    dec = SpecDecoder(jp, jc, jp, jc, k=4, max_len=128)
    ref = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 10)[0][0])
    eng = Engine(jp, jc, jp, jc, mode="pard", k=4, max_batch=1, max_len=128)
    eng.submit(p, 10)
    out = eng.run()[0]
    assert np.array_equal(ref, out.tokens)
