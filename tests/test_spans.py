"""Spans, scopes and counters of the serving engine (serving/spans.py):
device scopes in the fused step's op metadata, the Pallas kernels' names,
the host phase counters and spans, the host-overhead samples of a loop run
one step a call, and the paged-KV page counts."""
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import (decode_attention, flash_attention, pard_attention,
                           ssd, tree_attention)
from repro.models import init_params
from repro.serving import spans
from repro.serving.engine import Engine, EngineConfig


@pytest.fixture(scope="module")
def models():
    tc = get_config("tiny-target")
    dc = get_config("tiny-draft")
    tp = init_params(jax.random.PRNGKey(0), tc)
    dp = init_params(jax.random.PRNGKey(1), dc)
    return tc, tp, dc, dp


def _engine(models, **kw):
    tc, tp, dc, dp = models
    fields = dict(mode="pard", k=4, max_batch=2, max_len=256,
                  kv_block_size=16)
    fields.update(kw)
    return Engine(tp, tc, dp, dc, config=EngineConfig(**fields))


def _submit(eng, lengths, max_new=8, seed=0):
    rng = np.random.default_rng(seed)
    for n in lengths:
        eng.submit(rng.integers(0, 512, size=n).astype(np.int32), max_new)


# ------------------------------------------------------------ device scopes
@pytest.mark.parametrize("kind,scopes", [
    ("flat", (spans.DRAFT, spans.TARGET, spans.ACCEPT, spans.COMMIT,
              spans.STAGED)),
    ("tree", (spans.DRAFT, spans.TARGET, spans.ACCEPT, spans.COMMIT,
              spans.TREE_COMPACT, spans.STAGED)),
    ("ar", (spans.TARGET, spans.ACCEPT, spans.COMMIT, spans.STAGED)),
])
def test_step_hlo_carries_scopes(models, kind, scopes):
    kw = {"flat": {}, "tree": dict(tree=(2, 2, 1)), "ar": dict(mode="ar")}
    eng = _engine(models, **kw[kind])
    text = eng.ex.step_hlo(tree=kind == "tree")
    # the outermost program scope of every op that has one
    found = {m.split("/")[1] for m in re.findall(r'op_name="([^"]*)"', text)
             if m.startswith("jit(fused)/") and m.count("/") > 1}
    assert set(scopes) <= found, sorted(found)
    if kind != "tree":
        assert spans.TREE_COMPACT not in found
    if kind == "ar":
        assert spans.DRAFT not in found


# ------------------------------------------------------------ kernel names
def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


# each kernel at a tiny shape, lowered in interpret mode (nothing runs)
KERNELS = {
    "paged_decode_attention": (
        lambda q, kp, vp, bt, kl, qp: decode_attention.decode_attention_paged(
            q, kp, vp, bt, kl, qp, interpret=True),
        (_sds(2, 5, 4, 32), _sds(9, 16, 2, 32), _sds(9, 16, 2, 32),
         _i32(2, 4), _i32(2), _i32(2, 5))),
    "paged_latent_attention": (
        lambda q, pages, bt, kl, qp: decode_attention.paged_latent_attention(
            q, pages, bt, kl, qp, rank=32, scale=0.2, interpret=True),
        (_sds(2, 5, 4, 48), _sds(9, 16, 128), _i32(2, 4), _i32(2),
         _i32(2, 5))),
    "paged_tree_attention": (
        lambda q, kp, vp, bt, kl, qp, ws, anc:
            tree_attention.tree_attention_paged(
                q, kp, vp, bt, kl, qp, ws, anc, interpret=True),
        (_sds(2, 5, 4, 32), _sds(9, 16, 2, 32), _sds(9, 16, 2, 32),
         _i32(2, 4), _i32(2), _i32(2, 5), _i32(2),
         jax.ShapeDtypeStruct((2, 5), jnp.uint32))),
    "flash_attention": (
        lambda q, k, v: flash_attention.flash_attention(
            q, k, v, block_q=32, block_k=32, interpret=True),
        (_sds(1, 64, 4, 32), _sds(1, 64, 2, 32), _sds(1, 64, 2, 32))),
    "pard_attention": (
        lambda q, k, v, seg, base: pard_attention.pard_attention(
            q, k, v, seg, base, block_q=32, interpret=True),
        (_sds(1, 64, 2, 32), _sds(1, 64, 2, 32), _sds(1, 64, 2, 32),
         _i32(1, 64), _i32(1, 64))),
    "ssd": (
        lambda x, dt, a, b, c: ssd.ssd_chunked_kernel(
            x, dt, a, b, c, chunk=16, interpret=True),
        (_sds(1, 32, 2, 8), _sds(1, 32, 2), _sds(2), _sds(1, 32, 4),
         _sds(1, 32, 4))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_lowering_carries_its_name(name):
    fn, args = KERNELS[name]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # the kernel's name is the scope of its pallas_call
    calls = set(re.findall(r"/(\w+)/pallas_call\"", text))
    assert calls == {name}


# ------------------------------------------------------------ host phases
def test_phase_counters_cover_a_run_within_its_wall_time(models):
    eng = _engine(models)
    _submit(eng, (7, 20, 33))
    t0 = time.perf_counter_ns()
    eng.run()
    wall = time.perf_counter_ns() - t0
    got = {p: eng.stats[p + "_ns"] for p in spans.PHASES}
    assert all(isinstance(v, int) and v > 0 for v in got.values()), got
    assert sum(got.values()) <= wall


def test_sliced_run_records_host_overhead_per_step(models):
    """A loop run one step a call (as the chip benchmark drives it) records
    a harvest -> dispatch sample for every step after the first; a loop
    that drains clears the stamp, so the wait for new requests is never a
    sample."""
    eng = _engine(models)
    _submit(eng, (7, 20, 33))
    while eng.sched.has_work():
        eng.run(max_steps=eng.stats["steps"] + 1)
    steps = eng.stats["steps"]
    assert steps > 3
    assert len(eng.sched.host_overhead_ms) >= steps - 1
    assert eng.sched._harvest_done_ns is None
    _submit(eng, (9,), seed=1)
    eng.run(max_steps=eng.stats["steps"] + 1)
    assert len(eng.sched.host_overhead_ms) == steps - 1


def test_phase_spans_in_a_profiler_trace(models, tmp_path):
    """Each phase is a ``repro.<phase>`` span on the host plane; dispatch
    and harvest_wait carry the step number (in the pipelined loop the
    harvest of step t follows the dispatch of step t + 1), admit the count
    it admitted."""
    from jax.profiler import ProfileData
    eng = _engine(models, pipelined=True)
    _submit(eng, (7, 20))
    eng.run(max_steps=1)             # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    seen = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                seen += [(e.start_ns, e.name, dict(e.stats))
                         for e in line.events
                         if e.name.startswith(spans.PREFIX)]
    seen.sort(key=lambda s: s[0])
    names = {n[len(spans.PREFIX):] for _, n, _ in seen}
    assert names == set(spans.PHASES)
    order = [(n, st.get("step")) for _, n, st in seen
             if n in ("repro.dispatch", "repro.harvest_wait")]
    dispatched = [s for n, s in order if n == "repro.dispatch"]
    harvested = [s for n, s in order if n == "repro.harvest_wait"]
    assert dispatched == list(range(1, eng.stats["steps"]))
    assert sorted(harvested) == dispatched
    # depth 2: step t + 1 is dispatched before step t is harvested
    assert order.index(("repro.dispatch", 2)) \
        < order.index(("repro.harvest_wait", 1))
    assert sum(st["admitted"] for _, n, st in seen
               if n == "repro.admit") == 0   # both admitted before


# ------------------------------------------------------------- page counts
def test_kv_page_counts_match_a_hand_count(models):
    """Flat k = 4: verify window 5, prompt chunk 5; pages of 8 slots, a
    table of 128 / 8 = 16 entries, 2 rows. Prompts of 6 and 21 tokens.
    Step 0: both rows prefill from cursor 0, kv_len 0 + 5 -> 1 page each.
    Step 1: row 0 decodes from its last token (6 committed: window starts
    at 5, kv_len 10 -> 2 pages); row 1 prefills from cursor 5 (kv_len 10
    -> 2 pages)."""
    eng = _engine(models, kv_block_size=8, max_len=128)
    _submit(eng, (6, 21))
    eng.run(max_steps=1)
    assert (eng.stats["kv_pages_live"], eng.stats["kv_pages_grid"]) == (2, 32)
    eng.run(max_steps=2)
    assert (eng.stats["kv_pages_live"], eng.stats["kv_pages_grid"]) == (6, 64)
    rep = eng.sched.replicas[0]
    assert rep.slot_pf[0] == rep.slot_pf_len[0]      # row 0 decoded
    assert rep.slot_pf[1] < rep.slot_pf_len[1]       # row 1 prefilled


@pytest.mark.parametrize("block,group,tree", [(16, 32, None), (64, 8, None),
                                              (64, 8, (2, 2, 1))])
def test_kv_pages_grid_counts_whole_groups(block, group, tree):
    """A pool the page sweep copies itself (bf16, 2 kv heads of 128): a
    page of K and V is ``block`` x 1 KiB, so ``GROUP_BYTES`` (768 KiB) holds
    ``group`` pages. Occupied rows of 1, ``group`` and ``group`` + 1 live
    pages, one row empty: ``kv_pages_grid`` adds the live pages rounded up
    to whole groups, 1 + 1 + 2 groups. Tree verification's kernel steps
    over every table entry of every row instead."""
    cfg = dataclasses.replace(get_config("tiny-target"), num_layers=1,
                              d_model=256, n_heads=4, n_kv_heads=2,
                              head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, params, cfg, config=EngineConfig(
        mode="pard", k=4, max_batch=4, max_len=2048, kv_block_size=block,
        tree=tree))
    rep = eng.sched.replicas[0]
    table_w = rep.alloc.tables.shape[1]
    swept = decode_attention.sweep_group(
        [eng.ex.state.tcache["scan"][0]["k"][0]] * 2, table_w)
    assert swept == group
    assert rep.kv_group == (0 if tree else group)
    width = eng.sched.dec.verify_width
    live = np.asarray([1, group, group + 1, 0])
    # the window starts at the last committed token: kv_len = n - 1 + width
    rep.slot_last_n[:] = live * block - width + 1
    eng.sched._count_kv_pages(rep, live > 0, any_prefilling=False)
    assert eng.stats["kv_pages_live"] == 2 * group + 2
    assert eng.stats["kv_pages_grid"] == (4 * table_w if tree else 4 * group)


@pytest.mark.parametrize("mode", ["ar", "pard"])
def test_dense_step_carries_no_moe_count(models, mode):
    """Neither model has a MoE layer: the state holds no ``moe_rows`` and
    the fused step returns no count (the step a dense model compiled
    before the counter existed); ``stats["moe_rows"]`` stays 0."""
    eng = _engine(models, mode=mode)
    assert eng.ex.state.moe_rows is None
    assert eng.ex.dispatch().moe_rows is None
    _submit(eng, (6, 9))
    eng.run()
    assert eng.stats["moe_rows"] == 0


# ---------------------------------------------- latent attention + experts
def _mla_moe_engine(held=0, zero_router=False, **kw):
    """deepseek-v2-lite cut to two layers (dense, then MoE over 4 experts,
    top 2) serving itself; ``held`` experts on this device (0: all)."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                              moe_experts_held=held)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if zero_router:
        # every logit equal: top-2 picks experts 0 and 1 for every token
        mlp = params["scan"][1]["mlp"]            # the MoE layer, stacked
        mlp["router"] = jnp.zeros_like(mlp["router"])
    fields = dict(mode="pard", k=4, max_batch=2, max_len=256,
                  kv_block_size=16)
    fields.update(kw)
    return cfg, Engine(params, cfg, params, cfg,
                       config=EngineConfig(**fields))


def test_mla_moe_step_carries_its_layer_scopes():
    """Inside both forwards of the compiled step: the absorbed latent
    attention, the router and the held experts each under their scope."""
    _, eng = _mla_moe_engine()
    text = eng.ex.step_hlo()
    paths = [m for m in re.findall(r'op_name="([^"]*)"', text)
             if m.startswith("jit(fused)/")]
    for fwd in (spans.DRAFT, spans.TARGET):
        inner = {part for m in paths if m.split("/")[1] == fwd
                 for part in m.split("/")[2:]}
        assert set(spans.LAYER_SCOPES) <= inner, (fwd, sorted(inner))


@pytest.mark.parametrize("held,zero_router,per_token", [
    (0, False, 2),      # every expert held: all top-2 pairs
    (1, True, 1),       # expert 0 alone, which every token picks
    (1, False, None),   # expert 0 alone, routed as the weights decide
])
def test_moe_rows_count_the_pairs_routed_to_held_experts(held, zero_router,
                                                         per_token):
    """``stats["moe_rows"]``: the expert rows the step's forwards computed,
    one per (token, slot) routed to a held expert, every row of the batch
    included (pads and frozen rows run through the forward too). Per step
    the draft forward carries max_batch x 2k tokens and the verify forward
    max_batch x (k + 1), through the one MoE layer."""
    from repro.models import layers as L
    cfg, eng = _mla_moe_engine(held, zero_router)
    _submit(eng, (7, 20), max_new=12)
    eng.run()
    steps = eng.stats["steps"]
    tokens = steps * 2 * (2 * 4 + 4 + 1)
    if per_token is not None:
        assert eng.stats["moe_rows"] == tokens * per_token
    else:
        # a random router sends some pairs elsewhere, none twice
        assert 0 < eng.stats["moe_rows"] < tokens * cfg.moe_top_k
    # the layer's own count agrees with a host count of its routing
    p = jax.tree.map(lambda w: w[0], eng.ex.dec.tp["scan"][1]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    _, _, idx = L.moe_route(p, x, cfg)
    _, rows = L.moe_held_experts(p, x, *L.moe_route(p, x, cfg)[1:])
    assert int(rows) == int((np.asarray(idx) < cfg.resolved_experts_held
                             ).sum())
