"""The served Pallas kernels compile for a TPU v5e at published widths.

No chip is needed: the TPU compiler is installed and compiles for a
described, unattached ``v5e:2x2`` topology. The paged decode and tree
verify kernels — bf16 and int8 pools in the form the layer loop hands
them, at the verify windows the serving steps use — are compiled at
``llama3.2-1b`` widths on one chip and at ``llama3.1-8b`` widths split
four ways (the per-device shard of a tp=4 step), and the compiled program
must hold the kernel (``tpu_custom_call``).
This catches what interpret mode cannot: block shapes the TPU tiling rule
refuses, and more VMEM than a kernel may use. The AR training loss is
differentiated and compiled for the same chip, with the attention path the
platform picks on a TPU: a Pallas kernel there has no VJP.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

B, BLOCK, BLOCKS_PER_ROW = 8, 64, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for an unattached chip cannot be read back from the
    # persistent cache: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _widths(model, tp):
    cfg = get_config(model)
    return (cfg.num_layers, cfg.n_heads // tp, cfg.n_kv_heads // tp,
            cfg.resolved_head_dim)


@pytest.mark.parametrize("model,tp", [("llama3.2-1b", 1),
                                      ("llama3.1-8b", 4)])
@pytest.mark.parametrize("kernel,tq", [("decode", 9), ("tree", 23)])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_kernel_compiles_for_v5e(one_chip, model, tp, kernel, tq, kv):
    n_layers, hq, hkv, d = _widths(model, tp)
    # the layer loop hands a kernel every layer's bf16 pool viewed as one
    # (models.attention.layer_pages), and one layer's slice of an int8 pool
    nb = (1 + B * BLOCKS_PER_ROW) * (n_layers if kv == "bf16" else 1)
    kv_dtype = jnp.bfloat16 if kv == "bf16" else jnp.int8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [s((B, tq, hq, d), jnp.bfloat16),
            s((nb, BLOCK, hkv, d), kv_dtype), s((nb, BLOCK, hkv, d), kv_dtype),
            s((B, BLOCKS_PER_ROW), jnp.int32), s((B,), jnp.int32),
            s((B, tq), jnp.int32)]
    if kernel == "tree":
        args += [s((B,), jnp.int32), s((B, tq), jnp.uint32),
                 s((B,), jnp.int32)]
    scales = ([s((nb, BLOCK, hkv), jnp.float32)] * 2 if kv == "int8"
              else [None, None])

    def step(*a):
        ks, vs = a[len(args):] if kv == "int8" else (None, None)
        if kernel == "tree":
            q, kp, vp, bt, kl, qp, ws, anc, wl = a[:len(args)]
            return ops.tree_attention_paged(
                q, kp, vp, bt, kl, qp, ws, anc, win_len=wl, k_scale=ks,
                v_scale=vs, interpret=False)
        q, kp, vp, bt, kl, qp = a[:len(args)]
        return ops.decode_attention_paged(q, kp, vp, bt, kl, qp, k_scale=ks,
                                          v_scale=vs, interpret=False)

    live = args + [x for x in scales if x is not None]
    compiled = jax.jit(step).lower(*live).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "paged_latent_attention"])
@pytest.mark.parametrize("tq", [16, 9])
def test_swept_kernel_compiles_for_v5e_at_cell_shapes(one_chip, kernel, tq):
    """The page-swept kernels at the benchmark cells' shapes: 32 rows, a
    table of 37 entries, the 16-token draft and 9-token verify windows.
    GQA at ``dsq-1.5b-selfdraft`` widths (12/2 heads of 128, a bf16 pool
    of 1,185 pages stacked over 28 layers), latent at
    ``dsv2-lite-ep8-selfdraft``'s (16 heads, 512 + 64 lanes in a 640-lane
    pool stacked over 16 layers). The program holds the kernel and no
    temporary as large as one layer's pool: the pool is read in place."""
    b, entries, pages = 32, 37, 1185

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    index = [s((b, entries), jnp.int32), s((b,), jnp.int32),
             s((b, tq), jnp.int32)]
    if kernel == "paged_decode_attention":
        layers, page = 28, (BLOCK, 2, 128)
        pool = s((layers * pages,) + page, jnp.bfloat16)
        args = [s((b, tq, 12, 128), jnp.bfloat16), pool, pool] + index

        def call(q, kp, vp, bt, kl, qp):
            return ops.decode_attention_paged(q, kp, vp, bt, kl, qp,
                                              interpret=False)
    else:
        layers, page = 16, (BLOCK, 640)
        pool = s((layers * pages,) + page, jnp.bfloat16)
        args = [s((b, tq, 16, 576), jnp.bfloat16), pool] + index

        def call(q, pool, bt, kl, qp):
            return ops.paged_latent_attention(q, pool, bt, kl, qp, rank=512,
                                              scale=0.1, interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert f"%{kernel}" in compiled.as_text()
    layer_bytes = pages * 2 * math.prod(page)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def test_ar_loss_grad_compiles_for_v5e(one_chip, monkeypatch):
    """``jax.grad`` of the AR loss at ``llama3.2-1b`` widths (depth cut to
    2 layers) compiles for one v5e with the attention path a TPU picks
    unpinned: the uncached training forward stays on the jnp path."""
    from repro.core.adaptation import ar_loss
    from repro.models import init_params

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("llama3.2-1b"), num_layers=2)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        shapes)
    tokens = jax.ShapeDtypeStruct((2, 129), jnp.int32, sharding=one_chip)
    grad = jax.jit(jax.grad(lambda p, t: ar_loss(p, cfg, t)[0]))
    compiled = grad.lower(params, tokens).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("kind", ["flat", "tree"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_step_updates_pools_in_place_on_v5e(one_chip, monkeypatch, kind,
                                                  kv):
    """The fused serving step, compiled for one v5e with the Pallas kernels
    in it, updates the stacked KV pools in place: no broadcast or copy of a
    whole pool leaf. On a TPU an 8-bit pool's default layout, and its
    scales', is not the row-major one the kernels take, so a stacked
    quantized pool handed whole to a kernel is relaid out around the layer
    loop: a copy that no CPU compile shows. Head widths are the served
    models' (2 kv heads of 128, 64-slot pages); depth, width and pool cut.
    The K/V leaves are checked: at this cut size the compiler stages the
    small float32 scale pools through VMEM, a relayout it does not make at
    the served sizes of the benchmark's pool."""
    from hlo_text import pool_shapes, whole_buffer_fills_and_copies
    from repro.models import init_params
    from repro.models.attention import set_attention_backend
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.executor import NO_LIMIT

    cfg = dataclasses.replace(get_config("tiny-target"), num_layers=2,
                              d_model=256, n_heads=4, n_kv_heads=2,
                              head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, params, cfg, config=EngineConfig(
        mode="pard", k=8, max_batch=4, max_len=512, kv_block_size=64,
        kv_dtype=kv, tree=(2, 2, 1) if kind == "tree" else None))
    shapes = {s for s in pool_shapes(eng.ex.state)
              if s[1].count(",") == 4}                  # [L, NB, bs, Hkv, D]
    assert len(shapes) == 1
    b = eng.ex.max_batch
    args = (eng.ex._params, eng.ex.state, jnp.zeros((b,), bool),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), NO_LIMIT, jnp.int32))
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)
    monkeypatch.setattr(ops, "_interpret", lambda flag: False)
    set_attention_backend("pallas")
    try:
        text = eng.ex._step_fn("decode", kind == "tree", False).lower(
            *args).compile().as_text()
    finally:
        set_attention_backend(None)
    assert "tpu_custom_call" in text
    assert whole_buffer_fills_and_copies(text, shapes) == []


def _cell_step(one_chip, monkeypatch, config, layers, blocks):
    """The fused serving step of a benchmark configuration
    (``benchmarks/chip/configs/<config>.json``, through its reference's
    ``model_config`` and weight layout, as shapes), at published widths cut
    to ``layers``, with the cell's engine sizes and a pool of ``blocks``
    pages, compiled for one v5e with the Pallas kernels in it. Returns the
    compiled text and the (dtype, dims) of the state's pool leaves."""
    import json
    import pathlib
    import sys

    from repro.models.attention import set_attention_backend
    from repro.models.config import ModelConfig
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.executor import NO_LIMIT
    bench = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    sys.path.insert(0, str(bench))
    from chipbench import registry
    cfg = json.loads((bench / "configs" / f"{config}.json").read_text())
    cfg["num_hidden_layers"] = layers
    ref = registry.reference(bench, cfg["model_type"])
    model = ModelConfig(**ref.model_config(cfg))
    params = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    # the engine allocates its pools: 2 pages here, ``blocks`` compiled
    eng = Engine(params, model, params, model, config=EngineConfig(
        mode="pard", k=8, max_batch=32, max_len=2322, kv_block_size=64,
        kv_dtype="bf16", kv_num_blocks=2))

    def pool(cache):
        def sds(axis):       # the page axis: 0, or 1 past the layer axis
            return lambda x: jax.ShapeDtypeStruct(
                x.shape[:axis] + (blocks,) + x.shape[axis + 1:], x.dtype,
                sharding=one_chip)
        return {"prefix": jax.tree.map(sds(0), cache["prefix"]),
                "scan": jax.tree.map(sds(1), cache["scan"])}

    st = eng.ex.state
    state = dataclasses.replace(st, tcache=pool(st.tcache),
                                dcache=pool(st.dcache))
    b = eng.ex.max_batch
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (eng.ex._params, jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
         jnp.full((b,), NO_LIMIT, jnp.int32)))
    monkeypatch.setattr(ops, "_interpret", lambda flag: False)
    set_attention_backend("pallas")
    try:
        text = eng.ex._step_fn("decode", False, False).lower(
            args[0], state, *args[1:]).compile().as_text()
    finally:
        set_attention_backend(None)
    shapes = {(str(x.dtype), ",".join(map(str, x.shape)))
              for c in (state.tcache, state.dcache)
              for x in jax.tree.leaves(c)}
    return text, shapes


def test_latent_moe_step_compiles_for_v5e(one_chip, monkeypatch):
    """``dsv2-lite-ep8-selfdraft`` at its published widths (depth cut to
    the dense layer and two MoE layers, the benchmark cell's pool of 1,185
    pages): the fused PARD step holds the latent kernel for every layer
    kind and model, grouped-matmul kernels for the held experts, no
    per-head K or V built from the pool (no array spans a row's gathered
    37 x 64 positions), and no whole-pool broadcast or copy."""
    import re

    from hlo_text import whole_buffer_fills_and_copies
    text, shapes = _cell_step(one_chip, monkeypatch,
                              "dsv2-lite-ep8-selfdraft", 3, 1185)
    calls = re.findall(r"%(\S+) = \S+ custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    # target and draft, the unrolled dense layer and the scanned MoE body
    assert sum(c.startswith("paged_latent_attention") for c in calls) == 4
    assert sum(c.startswith("gmm") for c in calls) == 6   # 3 a forward
    assert not any(c.startswith("paged_decode_attention") for c in calls)
    dims = re.findall(r"= \w+\[([\d,]+)\]", text)
    assert not [d for d in dims if "2368" in d.split(",")]
    assert {s for s in shapes if s[1].endswith(",64,640")} == shapes
    assert whole_buffer_fills_and_copies(text, shapes) == []


def test_gqa_step_keeps_its_kernel_for_v5e(one_chip, monkeypatch):
    """``dsq-1.5b-selfdraft`` (depth cut to 2 layers) still runs the GQA
    paged decode kernel, target and draft, with none of the latent or
    expert kernels, and updates its pools in place."""
    import re

    from hlo_text import whole_buffer_fills_and_copies
    text, shapes = _cell_step(one_chip, monkeypatch, "dsq-1.5b-selfdraft",
                              2, 1185)
    calls = re.findall(r"%(\S+) = \S+ custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 2
    assert all(c.startswith("paged_decode_attention") for c in calls)
    assert whole_buffer_fills_and_copies(text, shapes) == []
