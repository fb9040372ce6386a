"""Sharded multi-device serving (DESIGN.md §11).

Single-device portion (tier-1): spec resolution for the reduction-free
serving ruleset, paged-pool / quant-scale / draft shardings, mesh helpers,
and mesh-of-1 == no-mesh token identity.

Multi-device portion (CI shard-gate: REPRO_HOST_DEVICES=4): token identity
across mesh shapes 1/2/4 for mixed greedy + seeded-sampled batches over
bf16 and int8 pools, and through the pipelined loop.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import mesh as mesh_mod
from repro.models import init_params
from repro.serving import kv_pool
from repro.serving.config import EngineConfig, SamplingParams
from repro.serving.engine import Engine
from repro.sharding import specs

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 host devices (REPRO_HOST_DEVICES=4)")


@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _mesh1():
    return mesh_mod.make_host_mesh(model=1, data=1)


def _walk(tree, prefix=""):
    """Path/leaf pairs with PartitionSpec treated as a LEAF (it subclasses
    tuple, so the generic walkers would iterate into it)."""
    if isinstance(tree, P):
        yield prefix, tree
    elif isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk(v, f"{prefix}/{i}")
    elif tree is not None:
        yield prefix, tree


def _by_name(spec_tree, name):
    return [(p, s) for p, s in _walk(spec_tree)
            if p.rsplit("/", 1)[-1] == name]


# ----------------------------------------------------------- spec resolution
def test_serving_param_rules_shard_output_dims_only(models):
    tc, tp, dc, dp = models
    mesh = _mesh1()
    sp = specs.param_specs(tp, mesh, serving=True)
    found = {}
    for path, s in _walk(sp):
        found.setdefault(path.rsplit("/", 1)[-1], []).append((path, s))
    # projections shard their OUTPUT dim (heads / d_ff / d_model-out) —
    # never a contraction dim — so no partial-sum reduce can appear.
    # Scanned layers pad a leading None (the repeats axis): compare tails.
    def tail(s, n):
        assert all(a is None for a in s[:-n]), s
        return tuple(s[-n:])

    for p, s in found.get("wq", []):
        assert tail(s, 3) == (None, "model", None), (p, s)
    for p, s in found.get("wo", []):
        assert tail(s, 2) == (None, "model"), (p, s)
    for p, s in found.get("wi", []):
        assert tail(s, 2) == (None, "model"), (p, s)
    for p, s in found.get("embedding", []):
        assert s == P("model", None), (p, s)
    # norms replicate
    for p, s in found.get("scale", []):
        assert all(a is None for a in s), (p, s)
    assert found.get("wq") and found.get("wi"), "tiny-target layout changed?"


def test_paged_pool_specs_shard_kv_heads_and_scales(models):
    tc, _, dc, _ = models
    mesh = _mesh1()
    pool = kv_pool.init_paged_caches(tc, 2, 8, 16, dtype="int8")
    sp = specs.paged_cache_specs(pool, mesh)
    ks, kss = _by_name(sp, "k"), _by_name(sp, "k_scale")
    assert ks and kss, "quantized paged pool must carry k + k_scale leaves"
    for p, s in ks:                      # [.., NB, bs, Hkv, hd]
        assert s[-2] == "model" and s[-1] is None, (p, s)
        assert all(a is None for a in s[:-2]), (p, s)
    for p, s in kss:                     # [.., NB, bs, Hkv]
        assert s[-1] == "model", (p, s)
        assert all(a is None for a in s[:-1]), (p, s)


def test_draft_replicates(models):
    _, _, dc, dp = models
    sp = specs.replicated_specs(dp)
    leaves = list(_walk(sp))
    assert leaves and all(s == P() for _, s in leaves)


def test_host_mesh_validation():
    m = _mesh1()
    assert m.axis_names == ("data", "model")
    with pytest.raises(ValueError, match="must be >= 1"):
        mesh_mod.make_host_mesh(model=1, data=0)
    n = jax.device_count()
    with pytest.raises(ValueError, match="divide"):
        mesh_mod.make_host_mesh(model=n + 1)
    with pytest.raises(ValueError, match="needs"):
        mesh_mod.make_host_mesh(model=1, data=n + 1)


def test_ensure_host_devices_too_late(monkeypatch):
    # keep the env-flag mutation from leaking into other tests
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    # jax is long initialized by the time tests run: asking for more
    # devices than the live backend exposes must fail loudly
    with pytest.raises(RuntimeError, match="host devices"):
        mesh_mod.ensure_host_devices(jax.device_count() + 1)
    mesh_mod.ensure_host_devices(jax.device_count())    # no-op, satisfied


def test_config_builds_mesh_from_tp():
    cfg = EngineConfig(tp=1)
    assert cfg.mesh is None                # tp=1 = single-device serving
    with pytest.raises(ValueError, match="model"):
        EngineConfig(mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1), ("data",)))


# ------------------------------------------------------------ token identity
def _serve(models, mesh, kv_dtype="bf16", pipelined=False, n_req=4,
           max_new=12):
    tc, tp, dc, dp = models
    cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                      kv_dtype=kv_dtype, kv_block_size=16, seed=3,
                      pipelined=pipelined, mesh=mesh)
    eng = Engine(tp, tc, dp, dc, config=cfg)
    rng = np.random.default_rng(7)
    out_rids = {}
    for i in range(n_req):
        p = rng.integers(0, 512, size=int(rng.integers(4, 14))).astype(
            np.int32)
        # mixed batch: even rows greedy, odd rows sampled with pinned seeds
        sp = SamplingParams(max_new=max_new,
                            temperature=0.0 if i % 2 == 0 else 0.8,
                            seed=None if i % 2 == 0 else 100 + i)
        out_rids[eng.submit(p, params=sp)] = i
    return {out_rids[c.rid]: c.tokens for c in eng.run()}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_mesh_of_one_matches_no_mesh(models, kv_dtype):
    """A (1, 1) mesh engine — full sharded code path: serving rules,
    state shardings, pinned jit shardings — is token-identical to the
    meshless engine, over bf16 and int8 pools."""
    base = _serve(models, None, kv_dtype)
    one = _serve(models, _mesh1(), kv_dtype)
    assert base.keys() == one.keys()
    for i in base:
        assert np.array_equal(base[i], one[i]), f"request {i} diverged"


@needs4
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_token_identity_across_mesh_shapes(models, kv_dtype):
    """THE tentpole gate: meshes of 1, 2 and 4 devices produce bitwise-
    identical tokens for a mixed greedy + seeded-sampled batch, over bf16
    and int8 pools."""
    base = _serve(models, mesh_mod.make_host_mesh(model=1, data=1),
                  kv_dtype)
    for n in (2, 4):
        got = _serve(models, mesh_mod.make_host_mesh(model=n, data=1),
                     kv_dtype)
        assert base.keys() == got.keys()
        for i in base:
            assert np.array_equal(base[i], got[i]), \
                f"request {i} diverged on the {n}-device mesh"


@needs4
def test_sharded_pipelined_loop_identity(models):
    """The depth-2 dispatch/harvest pipeline (DESIGN.md §9) composes with
    tensor-parallel serving: same tokens as the synchronous tp=1 loop."""
    base = _serve(models, None, pipelined=False)
    got = _serve(models, mesh_mod.make_host_mesh(model=2, data=1),
                 pipelined=True)
    assert base.keys() == got.keys()
    for i in base:
        assert np.array_equal(base[i], got[i])


@needs4
@pytest.mark.parametrize("n", [2, 4])
def test_kernel_path_token_identity_across_meshes(models, n):
    """The Pallas kernels inside a sharded step run in a shard_map over the
    kv heads ("model"): split when the axis divides them (n=2 for the tiny
    target's 2 kv heads), replicated when it does not (n=4). Either way
    the kernel path gives the same tokens on a mesh of n as on one."""
    from repro.models.attention import set_attention_backend
    set_attention_backend("pallas")
    try:
        base = _serve(models, None)
        got = _serve(models, mesh_mod.make_host_mesh(model=n, data=1))
    finally:
        set_attention_backend(None)
    assert base.keys() == got.keys()
    for i in base:
        assert np.array_equal(base[i], got[i]), \
            f"request {i} diverged on the {n}-device mesh"
