"""Typed config surface (serving/config.py): EngineConfig construction and
validation, the legacy-kwargs deprecation shim, SamplingParams equivalence
with the loose submit keywords, and per-request seeds."""
import argparse
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving.config import EngineConfig, SamplingParams
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


# ---------------------------------------------------------------- config
def test_config_validation():
    with pytest.raises(AssertionError):
        EngineConfig(mode="beam")
    with pytest.raises(TypeError):
        EngineConfig(kv_layout="paged")     # one KV layout: no such field
    with pytest.raises(AssertionError, match="kv_dtype"):
        EngineConfig(kv_dtype="int4")
    with pytest.raises(AssertionError, match="PARD"):
        EngineConfig(mode="ar", tree=(2, 2, 1))
    with pytest.raises(ValueError, match="max_batch"):
        EngineConfig(max_batch=0)
    with pytest.raises(ValueError, match="temperature"):
        EngineConfig(temperature=-0.5)
    with pytest.raises(ValueError, match="tree_ewma"):
        EngineConfig(tree_ewma=0.0)
    with pytest.raises(ValueError, match="tp"):
        EngineConfig(tp=0)
    with pytest.raises(ValueError, match="dp"):
        EngineConfig(dp=0)


def test_config_dp_device_validation():
    """dp * tp must fit the available devices, and an explicit mesh must
    carry a 'data' axis of exactly dp replicas."""
    import jax

    n = jax.device_count()
    with pytest.raises(ValueError, match="devices"):
        EngineConfig(dp=n + 1)                  # auto-mesh can't fit
    one = np.array(jax.devices()[:1]).reshape(1)
    with pytest.raises(ValueError, match="data"):
        EngineConfig(dp=2, mesh=jax.sharding.Mesh(one, ("model",)))
    mesh11 = jax.sharding.Mesh(one.reshape(1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="data"):
        EngineConfig(dp=2, mesh=mesh11)         # axis size 1 != dp=2
    assert EngineConfig(dp=1, mesh=mesh11).dp == 1


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_dp1_identical_to_no_dp(models, kv_dtype):
    """dp=1 (explicit single-replica mesh) is the historical single-engine
    path bit-for-bit, over bf16 and int8 pools."""
    import jax

    tc, tp, dc, dp = models
    rng = np.random.default_rng(14)
    prompts = _prompts(rng, 4)
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    out = {}
    for name, mesh_arg in (("no_dp", None), ("dp1", mesh)):
        cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                           kv_dtype=kv_dtype, kv_block_size=16, seed=5,
                           dp=1, mesh=mesh_arg)
        eng = Engine(tp, tc, dp, dc, config=cfg)
        rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
        out[name] = {rids[c.rid]: c.tokens for c in eng.run()}
    for i in range(len(prompts)):
        assert np.array_equal(out["no_dp"][i], out["dp1"][i])


def test_config_adaptive_default_bank():
    from repro.core.spec_decode import TemplateBank
    cfg = EngineConfig(adaptive_tree=True, k=4)
    assert isinstance(cfg.tree, TemplateBank)
    assert cfg.tree.max_depth == 4


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="max_new"):
        SamplingParams(max_new=0)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError, match="max_new"):
        SamplingParams().merged(None)
    with pytest.raises(ValueError, match="conflicting"):
        SamplingParams(max_new=8).merged(9)
    assert SamplingParams(max_new=8).merged(8).max_new == 8
    assert SamplingParams().merged(5).max_new == 5


def test_from_args_round_trip():
    """The serve launcher's argparse namespace maps onto the same config as
    direct construction; string trees normalise to TreeTemplate."""
    ns = argparse.Namespace(
        mode="pard", k=4, max_batch=2, max_len=256, temperature=0.7,
        seed=3, kv_block_size=32, kv_dtype="bf16",
        tree="2,2,1", adaptive_tree=False, prefix_cache=False,
        pipelined=True)
    cfg = EngineConfig.from_args(ns)
    ref = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                       temperature=0.7, seed=3, kv_block_size=32,
                       tree=(2, 2, 1), pipelined=True)
    assert cfg.max_batch == ref.max_batch and cfg.pipelined
    assert cfg.temperature == ref.temperature and cfg.seed == ref.seed
    # both normalise to a one-template bank of the same shape
    assert cfg.tree is not None
    assert [tuple(t.branching) for t in cfg.tree.templates] \
        == [tuple(t.branching) for t in ref.tree.templates]
    # partial namespaces fall back to field defaults
    sparse = EngineConfig.from_args(argparse.Namespace(mode="ar"))
    assert sparse.mode == "ar" and sparse.max_batch == 4


# ------------------------------------------------------------ deprecation
def test_legacy_kwargs_warn_and_match_config(models):
    tc, tp, dc, dp = models
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, 3)

    with pytest.warns(DeprecationWarning, match="EngineConfig"):
        legacy = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                        max_len=256, kv_block_size=16)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)  # none expected
        cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                           kv_block_size=16)
        typed = Engine(tp, tc, dp, dc, config=cfg)

    out = {}
    for name, eng in (("legacy", legacy), ("typed", typed)):
        rids = {eng.submit(p, 12): i for i, p in enumerate(prompts)}
        out[name] = {rids[c.rid]: c.tokens for c in eng.run()}
    for i in range(len(prompts)):
        assert np.array_equal(out["legacy"][i], out["typed"][i])


def test_config_plus_legacy_kwargs_rejected(models):
    tc, tp, dc, dp = models
    with pytest.raises(TypeError, match="not both"):
        Engine(tp, tc, dp, dc, config=EngineConfig(), max_batch=2)


def test_unknown_kwarg_rejected(models):
    tc, tp, dc, dp = models
    with pytest.raises(TypeError), pytest.warns(DeprecationWarning):
        Engine(tp, tc, dp, dc, beam_width=4)


# --------------------------------------------------------- SamplingParams
def test_sampling_params_equivalent_to_kwargs(models):
    """A mixed greedy+sampled batch submitted via SamplingParams produces
    exactly the tokens of the loose-kwargs path."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, 4)
    temps = [0.0, 0.8, 0.0, 0.9]
    cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                       kv_block_size=16, seed=5)

    eng_kw = Engine(tp, tc, dp, dc, config=cfg)
    rids_kw = {eng_kw.submit(p, 12, temperature=t): i
               for i, (p, t) in enumerate(zip(prompts, temps))}
    out_kw = {rids_kw[c.rid]: c.tokens for c in eng_kw.run()}

    eng_sp = Engine(tp, tc, dp, dc, config=cfg)
    rids_sp = {eng_sp.submit(p, params=SamplingParams(max_new=12,
                                                      temperature=t)): i
               for i, (p, t) in enumerate(zip(prompts, temps))}
    out_sp = {rids_sp[c.rid]: c.tokens for c in eng_sp.run()}

    assert len(out_kw) == len(out_sp) == len(prompts)
    for i in range(len(prompts)):
        assert np.array_equal(out_kw[i], out_sp[i])


def test_params_with_loose_kwargs_rejected(models):
    tc, tp, dc, dp = models
    eng = Engine(tp, tc, dp, dc,
                 config=EngineConfig(max_batch=1, max_len=256))
    with pytest.raises(ValueError, match="SamplingParams"):
        eng.submit(np.arange(4, dtype=np.int32), temperature=0.5,
                   params=SamplingParams(max_new=8))


def test_per_request_seed_decouples_from_engine_seed(models):
    """SamplingParams.seed pins a sampled request's stream to the request:
    the same seed reproduces the same tokens under DIFFERENT engine seeds,
    while the engine-derived default stream does not."""
    tc, tp, dc, dp = models
    rng = np.random.default_rng(13)
    p = rng.integers(0, 512, size=8).astype(np.int32)

    def run(engine_seed, req_seed):
        cfg = EngineConfig(mode="pard", k=4, max_batch=1, max_len=256,
                           seed=engine_seed)
        eng = Engine(tp, tc, dp, dc, config=cfg)
        eng.submit(p, params=SamplingParams(max_new=16, temperature=0.9,
                                            seed=req_seed))
        return eng.run()[0].tokens

    pinned = [run(s, req_seed=123) for s in (0, 1, 2)]
    assert all(np.array_equal(pinned[0], t) for t in pinned[1:])
    floating = [run(s, req_seed=None) for s in (0, 1)]
    assert not np.array_equal(floating[0], floating[1])
