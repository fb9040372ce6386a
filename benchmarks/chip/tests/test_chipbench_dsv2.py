"""The DeepSeek-V2 configuration and its cell, on the CPU at a small size.

The cell's files load by name and build the program's configuration; the
reference's weights take the program's layout; the program's paged
forward (prefill chunks, then verify and decode windows through the latent
pool) and the engine's served tokens agree with the reference's full
forward; the held experts' shares of a layer add up to the uncut layer;
the harness runs a small cell of this block and finds it correct; and the
two metrics of this block read their hand counts.
"""
import json
import pathlib
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import flops, harness, registry  # noqa: E402
from chipbench import latent_moe_counts as lmc  # noqa: E402

CELL = "dsv2-lite.reason"
PUBLISHED = json.loads(
    (BENCH / "configs" / "dsv2-lite-ep8-selfdraft.json").read_text())
# every width cut, every mechanism kept: latent attention with YaRN (at 16
# rope channels pairs 2 .. 6 of 8 ramp), softmax top-6 routing without
# renormalisation over 16 published experts of which 4 are held, 2 shared
# experts, a dense first layer
TINY = dict(PUBLISHED, name="tiny-dsv2", hidden_size=64,
            intermediate_size=96, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, num_attention_heads=4,
            num_key_value_heads=4, moe_intermediate_size=32,
            n_routed_experts=4, n_routed_experts_published=16,
            num_hidden_layers=3, vocab_size=300, torch_dtype="float32")


def ref_module():
    return registry.reference(BENCH, "deepseek_v2")


def model(cfg):
    from repro.models.config import ModelConfig
    return ModelConfig(**ref_module().model_config(cfg))


def params_of(cfg, seed=0):
    import jax
    return ref_module().init_params(cfg, jax.random.PRNGKey(seed))


def test_the_cell_loads_and_builds_the_published_block():
    from repro.models.attention import mla_softmax_scale
    from repro.models.config import scan_plan
    from repro.models.layers import yarn_correction_range
    cell = registry.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["loop"] == "closed"
    assert {m["name"] for m in cell.per_layer} >= {
        "mfu_mla_moe.batch", "latent_attn_roofline.batch"}
    assert "mfu.batch" not in {m["name"] for m in cell.per_layer}
    cfg = model(cell.config)
    assert (cfg.moe_num_experts, cfg.moe_experts_held, cfg.moe_top_k) == (
        64, 8, 6)
    assert (cfg.num_layers, cfg.d_model, cfg.kv_lora_rank) == (16, 2048, 512)
    assert not cfg.moe_norm_topk and cfg.norm_eps == 1e-6
    plan = scan_plan(cfg)
    assert (len(plan.prefix), len(plan.period), plan.n_repeats) == (1, 1, 15)
    # DeepSeek-V2's YaRN at these widths: pairs 10 .. 23 ramp, and the
    # softmax scale is 192^-1/2 x mscale(40, 0.707)^2
    assert yarn_correction_range(cfg, 64) == (10, 23)
    assert mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    # the registry entry of the same model agrees with the cell's
    from repro.configs import get_config
    reg = get_config("deepseek-v2-lite-16b")
    for field in ("norm_eps", "yarn_factor", "yarn_mscale_all_dim",
                  "moe_norm_topk", "moe_num_experts"):
        assert getattr(reg, field) == getattr(cfg, field), field


def test_weights_take_the_program_layout():
    import jax

    from repro.models import init_params
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(lambda k: ref_module().init_params(TINY, k), key)
    theirs = jax.eval_shape(lambda k: init_params(k, model(TINY)), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [
        x.shape for x in jax.tree.leaves(theirs)]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_forward_agrees_with_the_reference(backend):
    """Prefill chunks, then PARD verify windows and a decode step through
    the paged latent pool, in float32: the logits of every position equal
    the reference's full forward. Tolerance 1e-4 on logits of order 4:
    float32 summation order (the absorbed form contracts over the latent
    width where the reference expands per-head keys first)."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward
    from repro.models.attention import set_attention_backend
    from repro.serving import kv_pool
    cfg, params = model(TINY), params_of(TINY)
    toks = jax.random.randint(jax.random.PRNGKey(1), (512,), 0, 300)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_module().logits(params, TINY, toks))
    pool = kv_pool.init_paged_caches(cfg, 1, 12, 16, dtype=jnp.float32)
    tables = jnp.asarray([[7, 2, 9, 4, 11]], jnp.int32)
    pos, got = 0, []
    set_attention_backend(backend)
    try:
        with jax.default_matmul_precision("highest"):
            for w in (16, 16, 9, 9, 9, 1):
                lg, pool, aux = forward(
                    params, cfg, toks[None, pos:pos + w], caches=pool,
                    cache_pos=jnp.asarray([pos], jnp.int32),
                    block_tables=tables, kv_block_size=16, dtype=jnp.float32)
                got.append(np.asarray(lg[0, :, :300]))
                pos += w
    finally:
        set_attention_backend(None)
    np.testing.assert_allclose(np.concatenate(got), want[:pos], atol=1e-4)


def _served(eng, prompts, max_new):
    from repro.serving.engine import SamplingParams
    for p in prompts:
        eng.submit(p, params=SamplingParams(max_new=max_new,
                                            temperature=0.0))
    eng.run()
    return [np.asarray(c.tokens) for c in sorted(eng.completions,
                                                 key=lambda c: c.rid)]


def test_engine_serves_what_the_reference_ranks_first():
    """The engine (PARD flat k=4, bf16 weights and latent pool, the model
    drafting for itself) serves tokens whose reference logit lies at most
    0.07 below the reference's best, 1e-3 on average, and draft acceptance
    stays above 1. Readings over weight seeds 3-8 (seed 3 here): bf16
    0.0028-0.0455 widest and 2.4e-5 to 8.9e-4 mean (bf16 rounding of
    logits of order 4 swings near-ties; seed 3 reads 0.0455 and 5.4e-4);
    the program's fp8 KV pool, one precision below, 0.10-0.43 and 4.1e-3
    to 1.26e-2, outside both on every seed."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import Engine, EngineConfig
    cfg_json = dict(TINY, torch_dtype="bfloat16")
    cfg, params = model(cfg_json), params_of(cfg_json, seed=3)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 300, size=n).astype(np.int32)
               for n in (11, 30, 7)]
    eng = Engine(params, cfg, params, cfg, config=EngineConfig(
        mode="pard", k=4, max_batch=2, max_len=128, kv_block_size=16))
    served = _served(eng, prompts, 40)
    gaps_of = jax.jit(lambda t: ref_module().next_token_gaps(
        params, cfg_json, t))
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, toks in zip(prompts, served):
            padded = np.zeros(512, np.int32)
            padded[:len(toks)] = toks
            g = np.asarray(gaps_of(jnp.asarray(padded)))
            gaps.append(g[len(p) - 1:len(toks) - 1])
    gaps = np.concatenate(gaps)
    assert len(gaps) == 120
    assert gaps.max() <= 0.07 and gaps.mean() <= 1e-3, (gaps.max(),
                                                        gaps.mean())
    assert eng.mean_accepted() > 1.0


def test_expert_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over 8 devices, 2 experts each of 16: each share
    routes over all 16 and computes its own experts' part; the 8 parts
    plus the shared experts, counted once, equal the reference's uncut
    layer (every expert held). Float32: summation order alone (1e-5)."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L
    uncut = dict(TINY, n_routed_experts=16)
    cfg = model(uncut)
    p = params_of(uncut, seed=5)["scan"][0]["mlp"]
    p = jax.tree.map(lambda w: w[0], p)                  # the first MoE layer
    x = jax.random.normal(jax.random.PRNGKey(6), (512, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref_module()._moe(x, p, uncut)
        _, gates, idx = L.moe_route(p, x, cfg)
        total = L.mlp_apply(p["shared"], x)
        rows = 0
        for share in range(8):
            held = {k: p[k][2 * share:2 * share + 2]
                    for k in ("we_i", "we_g", "we_o")}
            part, n = L.moe_held_experts(held, x, gates, idx,
                                         first_expert=2 * share)
            total, rows = total + part, rows + int(n)
    assert rows == 512 * cfg.moe_top_k          # every pair computed once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_progtrace_finds_the_layer_scopes_in_the_compiled_step(monkeypatch):
    """``progtrace.hlo_scopes`` names each op of the served step by the
    outermost of the scopes it is told of; told of the layer scopes, it
    finds every one of them in the compiled step of this block."""
    from chipbench import progtrace
    from repro.serving import spans
    from repro.serving.engine import Engine, EngineConfig
    cfg, params = model(TINY), params_of(TINY)
    eng = Engine(params, cfg, params, cfg, config=EngineConfig(
        mode="pard", k=4, max_batch=2, max_len=128, kv_block_size=16))
    text = eng.ex.step_hlo()
    monkeypatch.setattr(progtrace, "SCOPES", spans.LAYER_SCOPES)
    assert set(progtrace.hlo_scopes(text).values()) == set(
        spans.LAYER_SCOPES)


# ------------------------------------------------------------ the harness
@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A benchmark directory holding a tiny cell of this block next to the
    real readers, references and traffic."""
    d = tmp_path_factory.mktemp("bench")
    for sub in ("metrics", "references"):
        shutil.copytree(BENCH / sub, d / sub)
    shutil.copy(BENCH / "peaks.json", d / "peaks.json")
    for sub in ("configs", "traffic", "cells"):
        (d / sub).mkdir()
    (d / "configs" / "tiny-dsv2.json").write_text(
        json.dumps(dict(TINY, torch_dtype="bfloat16")))
    (d / "traffic" / "closed.json").write_text(json.dumps({
        "loop": "closed", "requests": 32, "block": 4,
        "queue_depth": "max_batch", "lead_s": 0.5,
        "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
        "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 60},
        "tokens": {"source": "markov", "branching": 4, "zipf_a": 1.3,
                   "determinism": 2.0, "corpus_seed": 0}}))
    (d / "cells" / "tiny.dsv2.json").write_text(json.dumps({
        "engine": {"mode": "pard", "k": 8, "max_batch": 4, "max_len": 128,
                   "kv_block_size": 16, "kv_dtype": "bf16"},
        # between the engine's readings of this block over weight seeds
        # 3-8 (bf16: 0.003-0.046 widest, 2e-5 to 9e-4 mean; fp8 KV:
        # 0.10-0.43 and 4e-3 to 1.3e-2)
        "check": {"sample": 4, "min_tokens": 16, "max_logit_gap": 0.1,
                  "mean_logit_gap": 0.002, "min_mean_accepted": 1.5}}))
    doc = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    doc["workloads"] = [{"name": "tiny.dsv2", "config": "tiny-dsv2",
                         "traffic": "closed", "chips": 1,
                         "why": "a test cell"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.dsv2"]
    (d / "BENCHMARK.json").write_text(json.dumps(doc))
    return d


@pytest.mark.parametrize("kv", ["bf16", "fp8"])
def test_a_run_of_this_block_is_correct_and_its_control_is_not(bench, kv):
    """The harness serves the tiny cell and scores it: correct with the
    served bf16 latent pool, not with the fp8 one (the precision below)."""
    cell = registry.load_cell("tiny.dsv2", bench_json=bench / "BENCHMARK.json",
                              bench_dir=bench)
    res = harness.run_cell(cell, 7, 2.0, False, t0=time.perf_counter(),
                           require_tpu=False,
                           engine_overrides={"kv_dtype": kv})
    assert res["correct"] == (kv == "bf16"), res["checks"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["checks"]["compared_tokens"]["value"] >= 16


# ------------------------------------------------------------ the metrics
def _hand_counts():
    """The cell's widths, counted by hand."""
    d, h, layers, v = 2048, 16, 16, 102400
    attn = (d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128
            + h * 128 * d)
    expert = 3 * d * 1408
    moe = d * 64 + 2 * expert + 6 * 8 / 64 * expert
    per_token = 2 * (layers * attn + 3 * d * 10944 + 15 * moe + d * v)
    per_key = 2 * layers * h * (576 + 512)
    return per_token, per_key


def _run(trace=None):
    cell = registry.load_cell(CELL)
    n, keys = 60000, 75_000_000
    # the harness's count of the same tokens (flops.py, a dense GQA block)
    out_flops = n * flops.matmul_flops(cell.config) + (
        keys * flops.attention_flops_per_key(cell.config))
    return SimpleNamespace(
        cell=cell, window_s=50.0, output_tokens=n, output_flops=out_flops,
        chips=1, trace=trace,
        stats=dict(steps=1000, accepted=40000, live_steps=32000),
        peak=lambda key: registry.peaks(BENCH, "TPU v5 lite")[key])


def test_whole_step_share_reads_the_hand_count():
    per_token, per_key = _hand_counts()
    run = _run()
    assert lmc.context_keys(run.cell.config, 60000,
                            run.output_flops) == 75_000_000
    want = 100.0 * (60000 * per_token + 75_000_000 * per_key) / 50.0 / 197e12
    got = registry.metric_reader(BENCH, "mfu_mla_moe.batch")(run)
    assert got == pytest.approx(want, rel=1e-12)
    assert 0 < got < 100


def test_latent_kernel_roofline_reads_the_hand_count():
    read = registry.metric_reader(BENCH, "latent_attn_roofline.batch")
    assert read(_run()) is None                     # no trace, no reading
    trace = dict(window_s=4.0, busy_s=3.5, pallas_s=1.5, device_ops=[
        ["fusion.7 fusion", 0.9],
        ["paged_latent_attention.24 custom-call tpu_custom_call", 0.25],
        ["ragged-dot-none.3 custom-call tpu_custom_call", 0.5],
        ["paged_latent_attention.25 custom-call tpu_custom_call", 0.15]])
    # context: 2 models x 16 layers x 1152 B x 75e6 keys / 2.25 accepted;
    # windows: 1000 steps x 32 rows x 16 layers x (16 + 9) queries x 16
    # heads x (576 + 512) x 2 B
    moved = (2 * 16 * 1152 * 75_000_000 / 2.25
             + 1000 * 32 * 16 * 25 * 16 * 1088 * 2)
    want = 100.0 * moved / (0.4 * 50.0 / 4.0) / 819e9
    assert read(_run(trace)) == pytest.approx(want, rel=1e-12)
    assert read(_run(dict(trace, device_ops=[["fusion.7 fusion", 1.0]]))) \
        is None


def test_readers_of_this_block_skip_other_blocks():
    run = _run()
    run.cell = SimpleNamespace(config={"hidden_size": 64}, engine={})
    for name in ("mfu_mla_moe.batch", "latent_attn_roofline.batch"):
        assert registry.metric_reader(BENCH, name)(run) is None
