"""Speculative decoding system tests: losslessness (the paper's core
guarantee), acceptance accounting, speculative sampling distribution
preservation, and SSM/hybrid rollback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.spec_decode import SpecDecoder
from repro.models import init_params


@pytest.fixture(scope="module")
def tiny():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


PROMPT = None


def _prompt(vocab, b=2, p=8, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, p), 0, vocab)


@pytest.mark.parametrize("mode", ["pard", "vsd"])
def test_greedy_lossless_random_draft(tiny, mode):
    """Even a totally uncorrelated draft must give bit-identical output."""
    tc, tp, dc, dp = tiny
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    prompt = _prompt(tc.vocab_size)
    ar, _ = dec.generate_ar(prompt, 32)
    sp, stats = dec.generate_spec(prompt, 32, mode=mode)
    assert bool(jnp.all(ar == sp))
    assert stats.tokens_generated == 32 * prompt.shape[0]


def test_self_draft_accepts_everything(tiny):
    """Draft == target -> VSD acceptance is exactly 1.0 and each iteration
    commits K+1 tokens."""
    tc, tp, _, _ = tiny
    dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
    prompt = _prompt(tc.vocab_size)
    ar, _ = dec.generate_ar(prompt, 40)
    sp, stats = dec.generate_spec(prompt, 40, mode="vsd")
    assert bool(jnp.all(ar == sp))
    assert stats.acceptance_rate == pytest.approx(1.0)
    assert stats.mean_accepted == pytest.approx(5.0)


def test_pard_one_draft_forward_per_iteration(tiny):
    """Eq. 4: PARD drafts once per iteration; VSD drafts K times (Eq. 3)."""
    tc, tp, dc, dp = tiny
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    prompt = _prompt(tc.vocab_size)
    _, s_pard = dec.generate_spec(prompt, 24, mode="pard")
    _, s_vsd = dec.generate_spec(prompt, 24, mode="vsd")
    assert s_pard.draft_forwards == s_pard.iterations
    assert s_vsd.draft_forwards == 4 * s_vsd.iterations


@pytest.mark.parametrize("arch", ["tiny-ssm", "jamba-1.5-large-398b-smoke"])
@pytest.mark.parametrize("mode", ["pard", "vsd"])
def test_ssm_hybrid_lossless(arch, mode):
    """SSM state rollback (per-token state gathering) keeps spec decoding
    lossless for recurrent and hybrid targets/drafts."""
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(3), cfg)
    dec = SpecDecoder(params, cfg, params, cfg, k=4, max_len=256)
    prompt = _prompt(cfg.vocab_size, seed=5)
    ar, _ = dec.generate_ar(prompt, 24)
    sp, _ = dec.generate_spec(prompt, 24, mode=mode)
    assert bool(jnp.all(ar == sp))


def test_speculative_sampling_preserves_distribution():
    """Leviathan acceptance identity: for ANY draft distribution q, the
    first committed token's induced distribution equals the target p.
    Tested directly on the extracted acceptance function with a small vocab
    and enough trials for a tight Monte-Carlo bound."""
    from repro.core.spec_decode import speculative_accept
    V, K = 8, 3
    key = jax.random.PRNGKey(0)
    p = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (V,)) * 1.5)
    q = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 2), (V,)) * 1.5)
    p_full = jnp.broadcast_to(p, (1, K + 1, V))
    qprob = jnp.broadcast_to(q, (1, K, V))

    trials = 4000

    @jax.jit
    def one(rng):
        r1, r2 = jax.random.split(rng)
        props = jax.random.categorical(r1, jnp.log(qprob))     # [1, K]
        a, accepted, commit = speculative_accept(p_full, qprob, props, r2)
        first = jnp.where(a[0] >= 1, props[0, 0], commit[0])
        return first

    keys = jax.random.split(jax.random.PRNGKey(7), trials)
    firsts = np.asarray(jax.vmap(one)(keys))
    emp = np.bincount(firsts, minlength=V) / trials
    tv = 0.5 * np.abs(emp - np.asarray(p)).sum()
    assert tv < 0.05, f"TV distance {tv} (emp={emp}, p={np.asarray(p)})"


def test_acceptance_histogram_monotone(tiny):
    """Acceptance of position j requires acceptance of j-1: the histogram
    must be non-increasing."""
    tc, tp, _, _ = tiny
    dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
    prompt = _prompt(tc.vocab_size)
    _, stats = dec.generate_spec(prompt, 40, mode="pard")
    h = list(stats.accept_hist)
    assert all(h[i] >= h[i + 1] for i in range(len(h) - 1))


def test_one_decode_state_constructor(tiny, monkeypatch):
    """``generate_*`` and the serving ``Executor`` both get their
    ``DecodeState`` from ``SpecDecoder.init_state``; sized alike (rows,
    token buffer, pool pages), the two states have one tree structure and
    one leaf shape and dtype apiece — a new state field lands in both."""
    from repro.serving.engine import Engine, EngineConfig
    tc, tp, dc, dp = tiny
    built = []
    orig = SpecDecoder.init_state

    def spy(self, *a, **kw):
        state = orig(self, *a, **kw)
        built.append(state)
        return state

    monkeypatch.setattr(SpecDecoder, "init_state", spy)
    prompt = _prompt(tc.vocab_size)
    b, p, max_new = prompt.shape[0], prompt.shape[1], 8
    # generate_spec's token buffer: prompt + max_new + the window slack
    gen_len = p + max_new + SpecDecoder(tp, tc, dp, dc, k=4).window_slack
    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=gen_len,
                      kv_block_size=16)
    dec.generate_spec(prompt, max_new, mode="pard")
    assert len(built) == 1
    nb = built[0].tcache["scan"][0]["k"].shape[1]
    eng = Engine(tp, tc, dp, dc, config=EngineConfig(
        mode="pard", k=4, max_batch=b, max_len=gen_len, kv_block_size=16,
        kv_num_blocks=nb))
    assert len(built) == 2 and eng.ex.state is built[1]
    gen_state, ex_state = built
    assert jax.tree.structure(gen_state) == jax.tree.structure(ex_state)
    for x, y in zip(jax.tree.leaves(gen_state), jax.tree.leaves(ex_state)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
    dec.generate_ar(prompt, max_new)
    assert len(built) == 3 and built[2].dcache is None   # AR: no draft pool
