"""Throughput tensor-parallel serving ruleset (DESIGN.md §13).

Single-device portion (tier-1): the two serving rulesets agree on every
leaf outside the declared divergent set, indivisible dims replicate (never
contraction-split) in the exact ruleset, the canonical-chunk feasibility
fallback replicates a contraction dim ROWPARALLEL_CHUNKS does not divide
even when the (smaller) mesh would, and ``rowparallel_einsum``'s inline
chunk emulation reproduces the documented f32-once combine.

Multi-device portion (CI shard-gate throughput leg,
REPRO_HOST_DEVICES=4): the empirical psum law — XLA CPU's bf16
all-reduce equals f32-upcast-sum-round-once — against a real 4-way psum,
and bitwise tp2/tp4-vs-tp1 greedy/sampled token identity of the
throughput ruleset over bf16 and int8 pools.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import mesh as mesh_mod
from repro.models import init_params
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


class _FakeMesh:
    """Just enough Mesh surface for spec resolution (axis sizes without
    instantiating devices this host does not have)."""
    def __init__(self, model):
        self.axis_names = ("data", "model")
        self.devices = np.empty((1, model))


def _walk(tree, prefix=""):
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


# ------------------------------------------------------------ rule agreement
def test_rulesets_agree_outside_divergent_leaves():
    """Only the contraction-side weights and the replicated embedding pair
    may differ between the serving rulesets — everything else (the
    column-parallel up-projections, norms, biases) must stay identical so
    the throughput ruleset inherits the exact ruleset's layout choices."""
    assert set(specs.THROUGHPUT_PARAM_RULES) == set(specs.SERVING_PARAM_RULES)
    for name, rule in specs.SERVING_PARAM_RULES.items():
        thr = specs.THROUGHPUT_PARAM_RULES[name]
        if name in specs.RULESET_DIVERGENT_LEAVES:
            assert thr != rule, f"{name}: declared divergent but identical"
        else:
            assert thr == rule, f"{name}: rulesets diverge off-list"
    assert specs.THROUGHPUT_MLP_WO_RULES != specs.SERVING_MLP_WO_RULES
    # the divergent leaves shard the CONTRACTION dim (axis 0 of the
    # trailing spec for 2-D wo/out_proj, the middle f dim for 3-D we_o)
    assert specs.THROUGHPUT_PARAM_RULES["wo"] == [("tp", None, None)]
    assert specs.THROUGHPUT_PARAM_RULES["we_o"] == [(None, "tp", None)]
    assert specs.THROUGHPUT_PARAM_RULES["out_proj"] == [("tp", None)]
    assert specs.THROUGHPUT_MLP_WO_RULES == [("tp", None)]
    # the tied embedding/unembed replicate (no vocab-parallel collectives)
    assert specs.THROUGHPUT_PARAM_RULES["embedding"] == [(None, None)]
    assert specs.THROUGHPUT_PARAM_RULES["unembed"] == [(None, None)]


def test_exact_ruleset_indivisible_dims_replicate(models):
    """tiny-draft has 2 heads / 2 kv-heads: on a (fake) 4-way model mesh
    the exact ruleset must REPLICATE those projections — its single
    output-dim candidate is infeasible and there is no contraction-dim
    fallback that could smuggle in a partial-sum reduce."""
    _, _, dc, dp = models
    sp = specs.param_specs(dp, _FakeMesh(4), serving=True)
    hits = 0
    for name in ("wq", "wk", "wv"):
        for path, s in _by_name(sp, name):
            assert all(a is None for a in s), (path, s)
            hits += 1
    assert hits, "tiny-draft attention layout changed?"


def test_exact_ruleset_never_shards_contraction(models):
    """On the feasible tiny-target tp4 layout the exact ruleset shards
    attention wo on its OUTPUT d_model dim, never the heads contraction."""
    tc, tp, _, _ = models
    sp = specs.param_specs(tp, _FakeMesh(4), serving=True)
    rows = _by_name(sp, "wo")
    assert rows
    for path, s in rows:
        n = 3 if "mixer" in path else 2
        assert tuple(s[-n:])[-1] == "model" and s[-n] is None, (path, s)


def test_canonical_chunk_feasibility(models):
    """A contraction dim that ROWPARALLEL_CHUNKS (=4) does not divide must
    replicate under the throughput ruleset EVEN on a 2-way mesh that would
    divide it — the chunk count, not the mesh, pins the numerics.
    tiny-draft attention has 2 heads: 2 %% 4 != 0, so its wo replicates at
    tp2; tiny-target's 4 heads shard."""
    tc, tp, dc, dp = models
    dsp = specs.param_specs(dp, _FakeMesh(2), serving=True,
                            ruleset="throughput")
    for path, s in _by_name(dsp, "wo"):
        if "mixer" in path:          # attention wo [H, hd, d], H=2
            assert all(a is None for a in s), (path, s)
    tsp = specs.param_specs(tp, _FakeMesh(2), serving=True,
                            ruleset="throughput")
    hits = 0
    for path, s in _by_name(tsp, "wo"):
        if "mixer" in path:          # attention wo [H, hd, d], H=4
            assert tuple(s[-3:]) == ("model", None, None), (path, s)
            hits += 1
        else:                        # mlp wo [f, d], f=256
            assert tuple(s[-2:]) == ("model", None), (path, s)
    assert hits


def test_param_specs_rejects_unknown_ruleset(models):
    tc, tp, _, _ = models
    with pytest.raises(ValueError, match="ruleset"):
        specs.param_specs(tp, _FakeMesh(2), serving=True, ruleset="fast")


def test_engine_config_validates_tp_ruleset():
    with pytest.raises(ValueError, match="tp_ruleset"):
        EngineConfig(tp_ruleset="megatron")
    assert EngineConfig(tp_ruleset="throughput").tp_ruleset == "throughput"


# ------------------------------------------------- rowparallel_einsum numerics
def _canonical(x, w, nc=4):
    """Reference canonical-chunk combine: bf16 partial per chunk, ONE
    f32-upcast sum, rounded to the compute dtype once."""
    parts = [jnp.einsum("bf,fd->bd", xc, wc)
             for xc, wc in zip(jnp.split(x, nc, axis=1),
                               jnp.split(w, nc, axis=0))]
    return sum(p.astype(jnp.float32) for p in parts).astype(x.dtype)


def test_rowparallel_einsum_no_mesh_is_plain_einsum():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (6, 64), jnp.bfloat16)
    w = jax.random.normal(k2, (64, 32), jnp.bfloat16)
    got = ops.rowparallel_einsum("bf,fd->bd", x, w, x_axis=-1, w_axis=0)
    assert jnp.array_equal(got, jnp.einsum("bf,fd->bd", x, w))


def test_rowparallel_einsum_chunk_emulation_matches_canonical():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (6, 64), jnp.bfloat16)
    w = jax.random.normal(k2, (64, 32), jnp.bfloat16)
    mesh = mesh_mod.make_host_mesh(model=1, data=1)
    with ops.activation_mesh(mesh, "throughput"):
        got = ops.rowparallel_einsum("bf,fd->bd", x, w, x_axis=-1, w_axis=0)
    ref = _canonical(x, w)
    assert jnp.array_equal(got, ref)
    # ... and stays within bf16 rounding noise of the whole contraction
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(jnp.einsum("bf,fd->bd", x, w),
                                          np.float32),
                               rtol=0.05, atol=0.5)


def test_rowparallel_einsum_indivisible_falls_back_bitwise():
    """A contraction dim 4 does not divide takes the gather path — plain
    whole contraction, bitwise equal to the no-ruleset einsum."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (6, 30), jnp.bfloat16)
    w = jax.random.normal(k2, (30, 32), jnp.bfloat16)
    mesh = mesh_mod.make_host_mesh(model=1, data=1)
    with ops.activation_mesh(mesh, "throughput"):
        got = ops.rowparallel_einsum("bf,fd->bd", x, w, x_axis=-1, w_axis=0)
    assert jnp.array_equal(got, jnp.einsum("bf,fd->bd", x, w))


@needs4
def test_psum_bf16_is_f32_upcast_sum_rounded_once():
    """The empirical law the throughput numerics were designed around:
    XLA CPU's bf16 all-reduce upcasts to f32, sums (order-free for 4
    bf16-valued terms — exact in f32), and rounds to bf16 once. The HLO
    shows the reduction computation ``promoted``; here it is pinned
    behaviorally against a real 4-way psum."""
    mesh = mesh_mod.make_host_mesh(model=4, data=1)
    rng = np.random.default_rng(0)
    parts = jnp.asarray(
        rng.normal(size=(4, 256)) * 10.0 ** rng.integers(-2, 3, (4, 256)),
        jnp.bfloat16)

    @jax.jit
    def psum4(p):
        f = jax.shard_map(lambda s: jax.lax.psum(s, "model"), mesh=mesh,
                          in_specs=P(("model",), None), out_specs=P())
        return f(p)

    # each shard holds a (1, 256) slice, so the psum'd output keeps the
    # collapsed leading axis at size 1
    got = psum4(parts).reshape(-1)
    ref = jnp.sum(parts.astype(jnp.float32), axis=0).astype(jnp.bfloat16)
    assert jnp.array_equal(got, ref)


# -------------------------------------------------------- cross-mesh identity
def _serve(models, mesh, kv_dtype, ruleset, n_req=4, max_new=12):
    tc, tp, dc, dp = models
    cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=256,
                       kv_dtype=kv_dtype, kv_block_size=16, seed=3,
                       mesh=mesh, tp_ruleset=ruleset)
    eng = Engine(tp, tc, dp, dc, config=cfg)
    rng = np.random.default_rng(7)
    out_rids = {}
    for i in range(n_req):
        p = rng.integers(0, 512, size=int(rng.integers(4, 14))).astype(
            np.int32)
        sp = SamplingParams(max_new=max_new,
                            temperature=0.0 if i % 2 == 0 else 0.8,
                            seed=None if i % 2 == 0 else 100 + i)
        out_rids[eng.submit(p, params=sp)] = i
    return {out_rids[c.rid]: c.tokens for c in eng.run()}


@needs4
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_throughput_identity_across_mesh_shapes(models, kv_dtype):
    """The throughput ruleset's canonical-chunk numerics make every mesh
    size round the same f32 partial sum once — greedy AND seeded-sampled
    completions at tp2/tp4 match the throughput-tp1 reference at >= 0.99
    positional exact-match (bitwise in practice), over bf16 and int8
    pools."""
    base = _serve(models, mesh_mod.make_host_mesh(model=1, data=1),
                  kv_dtype, "throughput")
    for n in (2, 4):
        got = _serve(models, mesh_mod.make_host_mesh(model=n, data=1),
                     kv_dtype, "throughput")
        assert base.keys() == got.keys()
        match = total = 0
        for i in base:
            a, b = np.asarray(base[i]), np.asarray(got[i])
            m = min(len(a), len(b))
            match += int(np.sum(a[:m] == b[:m]))
            total += max(len(a), len(b))
        rate = match / max(1, total)
        assert rate >= 0.99, \
            f"tp{n}/{kv_dtype}: exact-match rate {rate:.4f} < 0.99"
