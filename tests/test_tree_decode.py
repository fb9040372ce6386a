"""Tree-structured PARD drafting: losslessness vs AR (the core guarantee),
degenerate-template == flat-K token identity, accepted-length accounting,
and engine-level paged-KV isolation when batched requests accept different
tree paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.spec_decode import SpecDecoder, TreeTemplate
from repro.models import init_params
from repro.serving.engine import Engine

from decode_refs import assert_greedy_up_to_tie, greedy_reference


@pytest.fixture(scope="module")
def tiny():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _prompt(vocab, b=2, p=8, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, p), 0, vocab)


def test_template_construction():
    t = TreeTemplate.from_branching((3, 2, 1))
    assert t.max_depth == 3
    assert t.num_nodes == 3 + 6 + 6
    assert t.num_slots == t.num_nodes + 1
    # breadth-first: parents precede children, depths are non-decreasing
    assert all(t.parent[s] < s for s in range(1, t.num_slots))
    assert all(t.depth[t.parent[s]] == t.depth[s] - 1
               for s in range(1, t.num_slots))
    # ancestor bitmask: own bit plus exactly the parent's mask
    for s in range(1, t.num_slots):
        assert t.anc[s] == (t.anc[t.parent[s]] | np.uint32(1 << s))
    assert TreeTemplate.flat(4).is_chain and not t.is_chain


def test_template_too_large_rejected():
    with pytest.raises(AssertionError, match="window slots"):
        TreeTemplate.from_branching((4, 3, 1, 1))      # 41 slots > 32


def test_tree_rejects_ssm(tiny):
    """Sampled (temperature > 0) trees are supported now — only SSM targets
    still reject (no positional rollback for a packed window)."""
    tc, tp, dc, dp = tiny
    dec = SpecDecoder(tp, tc, dp, dc, temperature=0.7,
                      tree=TreeTemplate.flat(4))
    assert dec.tree is not None and dec.temperature == 0.7
    sc = get_config("tiny-ssm")
    sp = init_params(jax.random.PRNGKey(3), sc)
    with pytest.raises(NotImplementedError, match="SSM"):
        SpecDecoder(sp, sc, dp, dc, tree=TreeTemplate.flat(4))


@pytest.mark.parametrize("branching", [(2, 2, 2, 1), (3, 2, 1, 1), (4, 1)])
def test_tree_greedy_lossless_random_draft(tiny, branching):
    """Even a totally uncorrelated draft must give bit-identical output:
    every committed token is the target argmax given its committed prefix,
    whatever path the tree accepted."""
    tc, tp, dc, dp = tiny
    tree = TreeTemplate.from_branching(branching)
    dec = SpecDecoder(tp, tc, dp, dc, max_len=256, tree=tree)
    prompt = _prompt(tc.vocab_size)
    ar, _ = dec.generate_ar(prompt, 32)
    sp, stats = dec.generate_spec(prompt, 32, mode="pard")
    assert bool(jnp.all(ar == sp))
    assert stats.tokens_generated == 32 * prompt.shape[0]


def test_degenerate_tree_token_identical_to_flat(tiny):
    """branching (1,)*K must reproduce the flat-K PARD path token for
    token — the tree machinery collapses exactly onto today's chain."""
    tc, tp, dc, dp = tiny
    prompt = _prompt(tc.vocab_size)
    flat = SpecDecoder(tp, tc, dp, dc, k=4, max_len=256)
    ref, st_flat = flat.generate_spec(prompt, 32, mode="pard")
    chain = SpecDecoder(tp, tc, dp, dc, max_len=256,
                        tree=TreeTemplate.flat(4))
    out, st_chain = chain.generate_spec(prompt, 32, mode="pard")
    assert bool(jnp.all(ref == out))
    assert st_chain.mean_accepted == pytest.approx(st_flat.mean_accepted)


def test_tree_self_draft_accepts_at_least_chain(tiny):
    """Self-drafting (draft == target): depth-1 always matches, and a
    node's acceptance set is a superset of the chain's at every depth, so
    the tree's accepted length per step is >= 1 and the histogram is
    monotone."""
    tc, tp, _, _ = tiny
    dec = SpecDecoder(tp, tc, tp, tc, max_len=512,
                      tree=TreeTemplate.from_branching((2, 2, 2, 1)))
    prompt = _prompt(tc.vocab_size, b=4, p=10)
    _, stats = dec.generate_spec(prompt, 40, mode="pard")
    h = list(stats.accept_hist)
    assert all(h[i] >= h[i + 1] for i in range(len(h) - 1))
    assert stats.mean_accepted >= 2.0       # depth 1 matches every step


def test_tree_engine_matches_ar_reference(tiny):
    """Two batched ragged requests through the paged engine with tree
    drafting: each accepts its own tree paths, and each completion must
    match its single-request AR reference — no paged-KV cross-contamination
    through the shared pool or the compaction scatter."""
    tc, tp, dc, dp = tiny
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 512, size=int(n_tok)).astype(np.int32)
               for n_tok in rng.integers(4, 14, size=5)]
    refs = {}
    for i, p in enumerate(prompts):
        dec = SpecDecoder(tp, tc, tp, tc, k=4, max_len=256)
        refs[i] = np.asarray(dec.generate_ar(jnp.asarray(p)[None], 12)[0][0])
    # self-draft so acceptance is non-trivial (different requests really do
    # take different paths through the template)
    eng = Engine(tp, tc, tp, tc, mode="pard", k=4, max_batch=2, max_len=256,
                 kv_block_size=32,
                 tree=TreeTemplate.from_branching((2, 2, 2, 1)))
    rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    for c in comps:
        assert np.array_equal(refs[rids[c.rid]], c.tokens)
    assert eng.stats["accepted"] > 0
    assert eng.mean_accepted() > 1.0


def test_tree_engine_layouts_agree(tiny):
    """Tree drafting through the KV pool with a separate draft (3,2,1,1
    template, compaction of every accepted path) commits, in a ragged
    batch with chunked prefill, AR's greedy tokens. Request 0 meets a bf16
    near-tie at its 9th new token: the float32 forward ranks the engine's
    token first by 0.028 over AR's (1-token bf16 steps pick the other), so
    that row is held to the float32 greedy choice up to the tie."""
    tc, tp, dc, dp = tiny
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 512, size=int(n_tok)).astype(np.int32)
               for n_tok in rng.integers(4, 14, size=4)]
    eng = Engine(tp, tc, dp, dc, mode="pard", max_batch=2, max_len=256,
                 kv_block_size=32,
                 tree=TreeTemplate.from_branching((3, 2, 1, 1)))
    rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
    comps = eng.run()
    assert len(comps) == len(prompts)
    dec = SpecDecoder(tp, tc, dp, dc, max_len=256, kv_block_size=32,
                      tree=TreeTemplate.from_branching((3, 2, 1, 1)))
    for c in comps:
        i = rids[c.rid]
        if i == 0:
            assert_greedy_up_to_tie(dec, prompts[i], c.tokens, 12)
        else:
            assert np.array_equal(greedy_reference(dec, prompts[i], 12),
                                  c.tokens)
