"""The harness end to end on the CPU at a tiny size.

A tiny configuration, traffic mixes and cells are added as files in a
temporary benchmark directory, next to copies of the metric readers and the
reference: the harness finds them by name, with no edit to a file it has.
The look for a chip is skipped (``require_tpu=False``); everything else is
the run the benchmark makes. With the timed path broken underneath, a
token altered where it is produced, a step that returns its state
unchanged, rows left out of the step, or the program's lower-precision
(fp8) KV cache, ``correct`` comes out false.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, registry, traffic  # noqa: E402

MARKOV = {"source": "markov", "branching": 4, "zipf_a": 1.3,
          "determinism": 2.0, "corpus_seed": 0}
TINY = {"name": "tiny-qwen2", "source": "a test configuration",
        "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 500,
        "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16"}
ENGINE = {"mode": "pard", "k": 8, "max_batch": 4, "max_len": 128,
          "kv_block_size": 16, "kv_dtype": "bf16"}
# readings of the tiny cells (CPU, bf16 KV): the program's widest gap is
# 0.0006-0.05 and its mean gap 8e-6-5e-4; the fp8 KV control reads 0.24 and
# 2.4e-3, the float8-weight reference control 0.61 and 0.023; mean accepted
# 2.02-2.18 and with a broken draft 1.0, its tokens still correct
CHECK = {"sample": 4, "min_tokens": 16, "max_logit_gap": 0.1,
         "mean_logit_gap": 0.001, "min_mean_accepted": 1.5}
OPEN_END_TO_END = ("ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms")
OPEN_PER_LAYER = (("queue_wait_p95_ms.chat", "ms"), ("step_ms.chat", "ms"),
                  ("prefill_row_share.chat", "%"), ("idle_share.chat", "%"))
MIXES = {
    "closed": {"loop": "closed", "requests": 64, "block": 4,
               "queue_depth": "max_batch", "lead_s": 0.5,
               "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
               "output_len": {"dist": "lognormal", "median": 24,
                              "sigma": 0.5, "min": 8, "max": 60}},
    "open": {"loop": "open", "lead_s": 0.5, "drain_s": 3,
             "arrivals": {"process": "poisson", "rate_per_s": 20.0},
             "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                            "min": 4, "max": 60},
             "output_len": {"dist": "uniform", "min": 4, "max": 30}},
    "pre": {"loop": "preload", "requests": 4,
            "prompt_len": {"dist": "fixed", "value": 60},
            "output_len": {"dist": "fixed", "value": 400}},
}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A benchmark directory: the real readers and references, plus a
    configuration, mixes and cells that exist only here."""
    d = tmp_path_factory.mktemp("bench")
    shutil.copytree(BENCH / "metrics", d / "metrics")
    shutil.copytree(BENCH / "references", d / "references")
    shutil.copy(BENCH / "peaks.json", d / "peaks.json")
    for sub in ("configs", "traffic", "cells"):
        (d / sub).mkdir()
    (d / "configs" / "tiny.json").write_text(json.dumps(TINY))
    doc = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    doc["workloads"] = []
    for name, mix in MIXES.items():
        (d / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(mix, tokens=MARKOV)))
        engine = dict(ENGINE, max_len=480) if name == "pre" else ENGINE
        (d / "cells" / f"tiny.{name}.json").write_text(
            json.dumps({"engine": engine, "check": CHECK}))
        doc["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                 "traffic": name, "chips": 1,
                                 "why": "a test cell"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.closed", "tiny.pre"]
    # the open loop's metrics: reader files that no BENCHMARK.json cell uses
    doc["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny.open"]}
        for n in OPEN_END_TO_END]
    doc["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": "open loop", "moves": "ttft_p95_ms",
         "workloads": ["tiny.open"]} for n, u in OPEN_PER_LAYER]
    (d / "BENCHMARK.json").write_text(json.dumps(doc))
    return d


def run(bench, name, seed=5, seconds=2.0, trace=False, overrides=None,
        **kw):
    cell = registry.load_cell(name, bench_json=bench / "BENCHMARK.json",
                              bench_dir=bench)
    return harness.run_cell(cell, seed, seconds, trace,
                            t0=time.perf_counter(), require_tpu=False,
                            engine_overrides=overrides, **kw)


def test_registry_finds_files_added_by_name(bench):
    cell = registry.load_cell("tiny.open", bench_json=bench / "BENCHMARK.json",
                              bench_dir=bench)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["loop"] == "open"
    assert cell.engine["max_batch"] == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    assert "queue_wait_p95_ms.chat" in {m["name"] for m in cell.per_layer}
    with pytest.raises(registry.RegistryError):
        registry.load_cell("tiny.none", bench_json=bench / "BENCHMARK.json",
                           bench_dir=bench)


def test_weights_take_the_program_layout():
    import jax

    from repro.models import init_params
    from repro.models.config import ModelConfig
    ref = registry.reference(BENCH, "qwen2")
    cfg = ModelConfig(**ref.model_config(TINY))
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(lambda k: ref.init_params(TINY, k), key)
    theirs = jax.eval_shape(lambda k: init_params(k, cfg), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [
        x.shape for x in jax.tree.leaves(theirs)]


@pytest.mark.parametrize("name", ["closed", "open", "pre"])
def test_a_run_is_correct_and_reports_its_metrics(bench, name):
    res = run(bench, f"tiny.{name}")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {"tiny.open": {"ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
                          "setup_s"}}.get(f"tiny.{name}",
                                          {"output_tok_s", "setup_s"})
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["compared_tokens"]["value"] >= CHECK["min_tokens"]


def test_a_token_altered_where_it_is_produced(bench, monkeypatch):
    from repro.core import acceptance
    honest = acceptance.greedy_chain_accept

    def altered(logits, props):
        a, accepted, commit = honest(logits, props)
        return a, accepted, (commit + 1) % TINY["vocab_size"]

    monkeypatch.setattr(acceptance, "greedy_chain_accept", altered)
    res = run(bench, "tiny.closed")
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > CHECK["max_logit_gap"]


def test_a_step_that_returns_its_state_unchanged(bench, monkeypatch):
    from repro.serving import executor
    honest = executor.Executor._build_fused

    def frozen(self, *a, **kw):
        fused = honest(self, *a, **kw)

        def step(params, state, retire, tree_sel, limits):
            _, a, rank, rhist, live, _, _ = fused(params, state, retire,
                                                  tree_sel, limits)
            return state, a * 0, rank, rhist, live & False, state.n, state.gen
        return step

    monkeypatch.setattr(executor.Executor, "_build_fused", frozen)
    res = run(bench, "tiny.closed", seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["compared_tokens"]["value"] == 0


def test_rows_left_out_of_the_step(bench, monkeypatch):
    import jax.numpy as jnp

    from repro.serving import executor
    honest = executor.Executor._build_fused

    def half(self, *a, **kw):
        fused = honest(self, *a, **kw)

        def step(params, state, retire, tree_sel, limits):
            b = retire.shape[0]
            skip = jnp.arange(b) >= b // 2          # half the rows never run
            return fused(params, state, retire | skip, tree_sel, limits)
        return step

    monkeypatch.setattr(executor.Executor, "_build_fused", half)
    res = run(bench, "tiny.open", seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["unfinished_warmup_requests"]["value"] > 0


def test_a_broken_draft(bench):
    sys.path.insert(0, str(BENCH))
    import control
    res = run(bench, "tiny.closed", draft_of=control.negated_final_norm)
    assert not res["correct"]
    assert res["checks"]["mean_accepted"]["value"] < CHECK["min_mean_accepted"]
    assert res["checks"]["max_logit_gap"]["value"] <= CHECK["max_logit_gap"]


def test_the_closed_loop_window_starts_after_its_lead_in(bench, monkeypatch):
    starts = []
    honest = harness._drive

    def drive(drv, loop, until, **kw):
        starts.append((len(drv.records), drv.stats()["steps"]))
        return honest(drv, loop, until, **kw)

    monkeypatch.setattr(harness, "_drive", drive)
    res = run(bench, "tiny.closed", seconds=1.0)
    assert res["correct"], res["checks"]
    (lead_reqs, lead_steps), (win_reqs, win_steps) = starts
    assert lead_reqs == 0 and win_reqs > ENGINE["max_batch"]
    assert win_steps > lead_steps


def test_the_lower_precision_control_is_not_correct(bench):
    res = run(bench, "tiny.pre", overrides={"kv_dtype": "fp8"})
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > CHECK["mean_logit_gap"]


def test_the_float8_reference_control_is_not_correct(bench):
    res = run(bench, "tiny.closed", reference_control=True)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    ctl = res["control"]
    assert ctl["compared_tokens"] == res["checks"]["compared_tokens"]["value"]
    assert ctl["mean_logit_gap"] > CHECK["mean_logit_gap"]
    assert ctl["max_logit_gap"] > CHECK["max_logit_gap"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "dsq-1.5b.reason", "--seed", str(2**33), "--seconds", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dsq-1.5b.reason", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_traffic_seed_reaches_the_weights():
    assert traffic.jax_seed(2**33 + 1) != traffic.jax_seed(2**33 + 2)
