"""One benchmark per paper table/figure (deliverable d).

Each function measures the real engine/decoder machinery on the trained tiny
model family and prints ``name,us_per_call,derived`` CSV rows. The paper's
corresponding numbers are attached as ``paper=`` fields in the derived
column for side-by-side validation of the ORDERINGS and RATIOS (absolute
TPS is CPU-bound here; see common.py).
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.eagle import EagleDecoder
from repro.core.spec_decode import SpecDecoder
from repro.core.spec_decode import row_tables
from repro.models import forward, init_caches
from repro.serving.engine import Engine

from . import common
from .common import emit, load_eagle, load_model, prompts, timed

MAX_NEW = 48
K = 8


def _tps(dec_fn, prompt, max_new=MAX_NEW):
    out, secs = timed(dec_fn, warmup=1, reps=1)
    toks = max_new * prompt.shape[0]
    return toks / secs, secs


def _ar_eager_tps(params, cfg, prompt, max_new=12):
    """The 'AR' baseline: op-by-op eager execution with a KV cache — the
    analogue of unoptimized HF transformers (the paper's AR row)."""
    with jax.disable_jit():
        b, p = prompt.shape
        nb, tables = row_tables(b, 256, 256, 64)
        caches = init_caches(cfg, b, nb, 64)
        kw = dict(block_tables=tables, kv_block_size=64)
        t0 = time.perf_counter()
        logits, caches, _ = forward(params, cfg, prompt, caches=caches,
                                    cache_pos=jnp.zeros((b,), jnp.int32),
                                    **kw)
        cur = jnp.argmax(logits[:, -1], -1)[:, None]
        for i in range(max_new - 1):
            logits, caches, _ = forward(
                params, cfg, cur.astype(jnp.int32), caches=caches,
                cache_pos=jnp.full((b,), p + i, jnp.int32), **kw)
            cur = jnp.argmax(logits[:, -1], -1)[:, None]
        jax.block_until_ready(cur)
        secs = time.perf_counter() - t0
    return max_new * b / secs


def table1() -> List:
    """Table 1: AR vs AR+ vs VSD vs PARD on the target/draft pair."""
    tp, tc = load_model("bench-target")
    dp, dc = load_model("bench-draft")
    pp, _ = load_model("pard_k8_r07", "bench-draft")
    prompt = prompts(4)
    rows = []

    ar_tps = _ar_eager_tps(tp, tc, prompt)
    dec = SpecDecoder(tp, tc, dp, dc, k=K, max_len=512)
    (_, s_arp) = timed(lambda: dec.generate_ar(prompt, MAX_NEW))
    arp_tps = MAX_NEW * 4 / s_arp

    (_, s_vsd) = timed(lambda: dec.generate_spec(prompt, MAX_NEW, mode="vsd"))
    (_, st_vsd) = dec.generate_spec(prompt, MAX_NEW, mode="vsd")
    vsd_tps = MAX_NEW * 4 / s_vsd

    decp = SpecDecoder(tp, tc, pp, dc, k=K, max_len=512)
    (_, s_pard) = timed(lambda: decp.generate_spec(prompt, MAX_NEW,
                                                   mode="pard"))
    (_, st_pard) = decp.generate_spec(prompt, MAX_NEW, mode="pard")
    pard_tps = MAX_NEW * 4 / s_pard

    rows.append(("table1.AR", 1e6 / ar_tps,
                 f"tps={ar_tps:.1f};speedup={ar_tps / arp_tps:.2f}x;paper=0.46x"))
    rows.append(("table1.AR+", 1e6 / arp_tps,
                 f"tps={arp_tps:.1f};speedup=1.00x;paper=1.00x"))
    rows.append(("table1.VSD", 1e6 / vsd_tps,
                 f"tps={vsd_tps:.1f};speedup={vsd_tps / arp_tps:.2f}x;"
                 f"acc={st_vsd.acceptance_rate:.2f};paper=2.31x"))
    rows.append(("table1.PARD", 1e6 / pard_tps,
                 f"tps={pard_tps:.1f};speedup={pard_tps / arp_tps:.2f}x;"
                 f"acc={st_pard.acceptance_rate:.2f};"
                 f"mean_acc={st_pard.mean_accepted:.2f};paper=3.57x"))
    emit(rows, "table1")
    return rows


def table2() -> List:
    """Table 2: target independence — ONE PARD draft accelerates the whole
    family (three target sizes, including draft==target size)."""
    dp, dc = load_model("bench-draft")
    pp, _ = load_model("pard_k8_r07", "bench-draft")
    prompt = prompts(4)
    rows = []
    for tname, paper in [("bench-target", "3.57x"), ("bench-mid", "2.81x"),
                         ("bench-draft", "2.17x")]:
        tp, tc = load_model(tname)
        dec = SpecDecoder(tp, tc, dp, dc, k=K, max_len=512)
        (_, s_arp) = timed(lambda: dec.generate_ar(prompt, MAX_NEW))
        arp = MAX_NEW * 4 / s_arp
        (_, s_vsd) = timed(lambda: dec.generate_spec(prompt, MAX_NEW,
                                                     mode="vsd"))
        decp = SpecDecoder(tp, tc, pp, dc, k=K, max_len=512)
        (_, s_pard) = timed(lambda: decp.generate_spec(prompt, MAX_NEW,
                                                       mode="pard"))
        vsd, pard = MAX_NEW * 4 / s_vsd, MAX_NEW * 4 / s_pard
        rows.append((f"table2.{tname}.VSD", 1e6 / vsd,
                     f"speedup={vsd / arp:.2f}x"))
        rows.append((f"table2.{tname}.PARD", 1e6 / pard,
                     f"speedup={pard / arp:.2f}x;paper={paper}"))
    emit(rows, "table2")
    return rows


def table3() -> List:
    """Table 3: method comparison in the serving framework (vLLM analogue):
    AR vs EAGLE vs VSD vs PARD at batch 1."""
    tp, tc = load_model("bench-target")
    dp, dc = load_model("bench-draft")
    pp, _ = load_model("pard_k8_r07", "bench-draft")
    ep = load_eagle(tc)
    prompt = prompts(1)
    rows = []

    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=512)
    (_, s_ar) = timed(lambda: dec.generate_ar(prompt, MAX_NEW))
    ar = MAX_NEW / s_ar

    ed = EagleDecoder(tp, tc, ep, k=4, max_len=512)
    (_, s_eag) = timed(lambda: ed.generate(prompt, MAX_NEW))
    _, st_e = ed.generate(prompt, MAX_NEW)
    (_, s_vsd) = timed(lambda: dec.generate_spec(prompt, MAX_NEW, mode="vsd"))
    decp = SpecDecoder(tp, tc, pp, dc, k=4, max_len=512)
    (_, s_pard) = timed(lambda: decp.generate_spec(prompt, MAX_NEW,
                                                   mode="pard"))
    eag, vsd, pard = (MAX_NEW / s for s in (s_eag, s_vsd, s_pard))
    rows.append(("table3.AR", 1e6 / ar, "speedup=1.00x;paper=1.00x"))
    rows.append(("table3.EAGLE", 1e6 / eag,
                 f"speedup={eag / ar:.2f}x;acc={st_e.acceptance_rate:.2f};"
                 f"paper=1.64x"))
    rows.append(("table3.VSD", 1e6 / vsd,
                 f"speedup={vsd / ar:.2f}x;paper=2.02x"))
    rows.append(("table3.PARD", 1e6 / pard,
                 f"speedup={pard / ar:.2f}x;paper=3.06x"))
    emit(rows, "table3")
    return rows


def table4() -> List:
    """Table 4: batch scaling 1..16 through the batched engine."""
    tp, tc = load_model("bench-target")
    dp, dc = load_model("bench-draft")
    pp, _ = load_model("pard_k8_r07", "bench-draft")
    rows = []
    paper = {1: "3.06x", 2: "2.59x", 4: "2.19x", 8: "1.55x", 16: "1.17x"}
    for bs in (1, 2, 4, 8, 16):
        prompt_np = np.asarray(prompts(bs))
        def run(mode, params, dcfg):
            eng = Engine(tp, tc, params, dcfg, mode=mode, k=4,
                         max_batch=bs, max_len=512)
            for r in range(bs):
                eng.submit(prompt_np[r], MAX_NEW)
            t0 = time.perf_counter()
            comps = eng.run()
            return sum(c.generated for c in comps) / (time.perf_counter() - t0)
        run("ar", dp, dc)                       # warm
        ar = run("ar", dp, dc)
        run("pard", pp, dc)
        pard = run("pard", pp, dc)
        rows.append((f"table4.bs{bs}.PARD", 1e6 / pard,
                     f"speedup={pard / ar:.2f}x;paper={paper[bs]}"))
    emit(rows, "table4")
    return rows


def table5() -> List:
    """Table 5: acceptance rates (1-alpha and 4-alpha) PARD vs EAGLE vs VSD."""
    tp, tc = load_model("bench-target")
    dp, dc = load_model("bench-draft")
    pp, _ = load_model("pard_k8_r07", "bench-draft")
    ep = load_eagle(tc)
    prompt = prompts(4)
    rows = []

    def k_alpha(hist, iters):
        h = np.asarray(hist, np.float64) / max(iters, 1)
        return h[0], float(np.mean(h[:4]))

    ed = EagleDecoder(tp, tc, ep, k=4, max_len=512)
    _, st = ed.generate(prompt, MAX_NEW)
    a1, a4 = k_alpha(st.accept_hist, st.iterations * 4)
    rows.append(("table5.EAGLE", 0.0,
                 f"1-alpha={a1:.2f};4-alpha={a4:.2f};paper=0.82/0.72"))

    decp = SpecDecoder(tp, tc, pp, dc, k=4, max_len=512)
    _, st = decp.generate_spec(prompt, MAX_NEW, mode="pard")
    a1, a4 = k_alpha(st.accept_hist, st.iterations * 4)
    rows.append(("table5.PARD", 0.0,
                 f"1-alpha={a1:.2f};4-alpha={a4:.2f};paper=0.90/0.88"))

    dec = SpecDecoder(tp, tc, dp, dc, k=4, max_len=512)
    _, st = dec.generate_spec(prompt, MAX_NEW, mode="vsd")
    a1, a4 = k_alpha(st.accept_hist, st.iterations * 4)
    rows.append(("table5.VSD", 0.0, f"1-alpha={a1:.2f};4-alpha={a4:.2f}"))
    emit(rows, "table5")
    return rows


def table6() -> List:
    """Table 6: draft-phase memory-bandwidth (analytic, bf16): bytes of
    draft weights streamed per speculative iteration. PARD is constant in k;
    AR drafts scale linearly. Computed for BOTH the tiny pair and the
    paper's actual LLaMA3.2-1B draft (param count from the config)."""
    from repro.configs import get_config
    from repro.launch.steps import param_shapes

    def param_bytes(cfg):
        sds = param_shapes(cfg)
        return sum(np.prod(s.shape) for s in jax.tree.leaves(sds)) * 2  # bf16

    rows = []
    for label, arch in [("bench-draft", "bench-draft"),
                        ("L3.2-1B", "llama3.2-1b")]:
        b = param_bytes(get_config(arch))
        for k in (4, 6, 8):
            vsd_gb = b * k / 1e9
            pard_gb = b / 1e9
            paper = {4: "2.48", 6: "2.48", 8: "2.48"}[k] \
                if label == "L3.2-1B" else "-"
            rows.append((f"table6.{label}.k{k}", 0.0,
                         f"vsd_draft_gb={vsd_gb:.2f};pard_draft_gb={pard_gb:.2f};"
                         f"paper_pard_gb={paper}"))
    emit(rows, "table6")
    return rows


def fig6a() -> List:
    """Fig 6a: COD ablation — training token cost vs final speed/acceptance
    for (r=0.7,rmin=0.2), (r=0.5,rmin=0.1), no-drop."""
    tp, tc = load_model("bench-target")
    _, dc = load_model("bench-draft")
    prompt = prompts(4)
    import json
    import os
    man = json.load(open(os.path.join(common.ART, "manifest.json")))
    rows = []
    for tag in ("pard_k8_r07", "pard_k8_r05", "pard_k8_nodrop"):
        pp, _ = load_model(tag, "bench-draft")
        dec = SpecDecoder(tp, tc, pp, dc, k=K, max_len=512)
        (_, secs) = timed(lambda: dec.generate_spec(prompt, MAX_NEW,
                                                    mode="pard"))
        _, st = dec.generate_spec(prompt, MAX_NEW, mode="pard")
        tokens = man["runs"].get(tag, {}).get("train_tokens", 0)
        rows.append((f"fig6a.{tag}", 1e6 * secs / (MAX_NEW * 4),
                     f"train_tokens={tokens};acc={st.acceptance_rate:.3f};"
                     f"mean_acc={st.mean_accepted:.2f}"))
    emit(rows, "fig6a")
    return rows


def fig6b() -> List:
    """Fig 6b: K_train x K_infer grid — extrapolation via the shared mask
    token (K_infer > K_train must still work)."""
    tp, tc = load_model("bench-target")
    _, dc = load_model("bench-draft")
    prompt = prompts(4)
    rows = []
    for ktr, tag in [(2, "pard_k2_r07"), (4, "pard_k4_r07"),
                     (8, "pard_k8_r07")]:
        pp, _ = load_model(tag, "bench-draft")
        for kinf in (2, 4, 8, 12):
            dec = SpecDecoder(tp, tc, pp, dc, k=kinf, max_len=512)
            (_, secs) = timed(lambda: dec.generate_spec(prompt, MAX_NEW,
                                                        mode="pard"))
            _, st = dec.generate_spec(prompt, MAX_NEW, mode="pard")
            tps = MAX_NEW * 4 / secs
            rows.append((f"fig6b.ktrain{ktr}.kinfer{kinf}", 1e6 / tps,
                         f"tps={tps:.1f};mean_acc={st.mean_accepted:.2f}"))
    emit(rows, "fig6b")
    return rows


def _recording_config(**overrides) -> dict:
    """Provenance stamp for a (re)recorded BENCH_serve section: the live
    EngineConfig defaults the recording ran under (plus any explicit
    overrides). serve_delta.py warns when a section's stamp no longer
    matches the current defaults — a stale recording predating an engine
    behavior change (exactly how the seed 'serve' numbers went stale
    against the PR 6 pipelined/greedy_only step variants)."""
    import dataclasses as _dc

    from repro.serving.config import EngineConfig
    cfg = {f.name: f.default for f in _dc.fields(EngineConfig)
           if f.name in ("kv_dtype", "pipelined", "tp_ruleset")}
    cfg.update(overrides)
    return cfg


def serve() -> List:
    """Serving-engine KV pool: tokens/sec and cache HBM bytes for
    ar/vsd/pard. Uses the tiny family (the point is the bytes — the peak in
    use tracks actual fill, not the pool's capacity — not absolute CPU
    throughput) and persists the trajectory to the
    canonical BENCH_serve.json at the repo root (common.update_bench_serve;
    the per-table results/ mirror is intentionally not written)."""
    from repro.serving.config import EngineConfig
    tp, tc = load_model("tiny-target")
    dp, dc = load_model("tiny-draft")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=8)]
    max_len, max_new = 1024, 24

    rows, record = [], {"config": _recording_config()}
    for mode in ("ar", "vsd", "pard"):
        eng = Engine(tp, tc, dp, dc, config=EngineConfig(
            mode=mode, k=4, max_batch=2, max_len=max_len,
            kv_block_size=64))
        for r in reqs:                      # warm pass: compile steps
            eng.submit(r, max_new)
        eng.run()
        eng.peak_kv_bytes_in_use = eng.kv_bytes_in_use()
        for r in reqs:
            eng.submit(r, max_new)
        t0 = time.perf_counter()
        comps = eng.run()
        wall = time.perf_counter() - t0
        tps = sum(c.generated for c in comps[len(reqs):]) / wall
        cap = eng.kv_capacity_bytes()
        peak = eng.peak_kv_bytes_in_use
        rows.append((f"serve.{mode}.paged", 1e6 / tps,
                     f"tps={tps:.1f};kv_capacity_mb={cap / 1e6:.2f};"
                     f"kv_peak_mb={peak / 1e6:.2f}"))
        record[f"{mode}.paged"] = dict(
            tokens_per_sec=round(tps, 2), kv_capacity_bytes=cap,
            kv_peak_bytes_in_use=peak)
    common.update_bench_serve("serve", record)
    emit(rows, "serve", persist=False)
    return rows


# tree templates benchmarked by serve_tree: the degenerate chain (asserted
# token-identical to the flat-K path) and the branching template that the
# CI smoke gate tracks.  PARD self-drafts here (draft == target weights):
# depth 1 always matches and the mask-chain conditioning error grows with
# depth — exactly the regime where top-k branches pay off — so accepted
# lengths are meaningful even without trained artifacts.
TREE_K = 4
TREE_TEMPLATES = {"chain-1x1x1x1": (1, 1, 1, 1), "tree-2x2x2x1": (2, 2, 2, 1)}


def serve_tree(temperature: float = 0.0) -> List:
    """Tree-structured PARD drafting through the serving engine: accepted
    length and tokens/sec per tree template vs the flat-K baseline, paged
    KV. Greedy (temperature 0): the degenerate single-branch template must
    be token-identical to flat-K, and the branching template must achieve
    strictly higher mean accepted length per verify step (both enforced
    here; CI gates the recorded floor via ``benchmarks.run --smoke-floor``).
    Sampled (temperature > 0, recorded under "tree_sampled"): acceptance is
    stochastic multi-round rejection sampling, so the token-identity and
    strict-ordering asserts do not apply — CI gates the recorded sampled
    mean accepted length floor instead (``--temperature 0.8
    --smoke-floor 1.3``; self-drafting keeps depth-1 q == p, so every step
    accepts at least one draft token and healthy runs sit well above)."""
    from repro.core.spec_decode import TreeTemplate
    tp, tc = load_model("tiny-target")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=6)]
    max_len, max_new = 512, 32
    sampled = temperature > 0.0
    section = "tree_sampled" if sampled else "tree"
    tag = f"serve_tree[T={temperature}]" if sampled else "serve_tree"

    def run_engine(tree):
        eng = Engine(tp, tc, tp, tc, mode="pard", k=TREE_K, max_batch=2,
                     max_len=max_len, temperature=temperature,
                     kv_block_size=64, tree=tree)
        for r in reqs:                          # warm pass: compile steps
            eng.submit(r, max_new)
        eng.run()
        eng.stats.update(accepted=0, live_steps=0)
        for r in reqs:
            eng.submit(r, max_new)
        t0 = time.perf_counter()
        comps = eng.run()
        wall = time.perf_counter() - t0
        toks = {c.rid: c.tokens for c in comps[len(reqs):]}
        tps = sum(c.generated for c in comps[len(reqs):]) / wall
        return toks, tps, eng.mean_accepted()

    rows, record = [], {}
    flat_toks, flat_tps, flat_acc = run_engine(None)
    rows.append((f"{tag}.flat-k{TREE_K}", 1e6 / flat_tps,
                 f"tps={flat_tps:.1f};mean_accepted={flat_acc:.3f}"))
    record[f"flat-k{TREE_K}"] = dict(tokens_per_sec=round(flat_tps, 2),
                                     mean_accepted=round(flat_acc, 4))
    if sampled:
        record[f"flat-k{TREE_K}"]["temperature"] = temperature
    for name, branching in TREE_TEMPLATES.items():
        toks, tps, acc = run_engine(TreeTemplate.from_branching(branching))
        rows.append((f"{tag}.{name}", 1e6 / tps,
                     f"tps={tps:.1f};mean_accepted={acc:.3f}"))
        record[name] = dict(tokens_per_sec=round(tps, 2),
                            mean_accepted=round(acc, 4),
                            branching=list(branching))
        if sampled:
            record[name]["temperature"] = temperature
        elif all(b == 1 for b in branching):
            # degenerate tree == flat-K, token for token
            same = (set(toks) == set(flat_toks) and
                    all(np.array_equal(toks[r], flat_toks[r]) for r in toks))
            assert same, "degenerate chain diverged from the flat-K path"
            record[name]["token_identical_to_flat"] = True
        else:
            assert acc > flat_acc, (
                f"branching template {branching} did not beat flat-K mean "
                f"accepted length ({acc:.3f} <= {flat_acc:.3f})")
    common.update_bench_serve(section, record)
    emit(rows, "serve_tree", persist=False)
    return rows


def serve_adaptive() -> List:
    """Adaptive per-request tree templates (DESIGN.md §7) vs the static
    (2,2,2,1) template that the CI smoke gate tracks: the same ragged
    self-draft workload through the paged engine, once pinned to the static
    tree and once with the acceptance-statistics controller selecting and
    reshaping per request from the default chain/balanced/wide bank. The
    run is fully deterministic (greedy, fixed seeds), and the controller
    must END UP no worse than the static shape — asserted here, with both
    mean accepted lengths recorded under BENCH_serve.json's
    "tree_adaptive" section so ``benchmarks.run --adaptive-tree
    --smoke-floor`` can gate the absolute level and serve_delta.py reports
    the tokens/sec trend."""
    from repro.core.spec_decode import TemplateBank, TreeTemplate
    tp, tc = load_model("tiny-target")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=8)]
    max_len, max_new = 512, 32

    def run_engine(tree, adaptive):
        eng = Engine(tp, tc, tp, tc, mode="pard", k=TREE_K, max_batch=2,
                     max_len=max_len, kv_block_size=64,
                     tree=tree, adaptive_tree=adaptive)
        for r in reqs:                          # warm pass: compile steps
            eng.submit(r, max_new)
        eng.run()
        # every recorded stat must cover the TIMED pass only (the warm
        # pass still seeds the controller's global EWMA, as serving would)
        eng.stats.update(accepted=0, live_steps=0, tree_switches=0,
                         tree_hist=np.zeros_like(eng.stats["tree_hist"]))
        for r in reqs:
            eng.submit(r, max_new)
        t0 = time.perf_counter()
        comps = eng.run()
        wall = time.perf_counter() - t0
        tps = sum(c.generated for c in comps[len(reqs):]) / wall
        return tps, eng.mean_accepted(), eng

    rows, record = [], {"config": _recording_config()}
    s_tps, s_acc, _ = run_engine(
        TreeTemplate.from_branching((2, 2, 2, 1)), False)
    rows.append(("serve_adaptive.static-2x2x2x1", 1e6 / s_tps,
                 f"tps={s_tps:.1f};mean_accepted={s_acc:.3f}"))
    record["static-2x2x2x1"] = dict(tokens_per_sec=round(s_tps, 2),
                                    mean_accepted=round(s_acc, 4))

    bank = TemplateBank.default(TREE_K)
    a_tps, a_acc, eng = run_engine(bank, True)
    hist = [int(h) for h in eng.stats["tree_hist"]]
    rows.append(("serve_adaptive.adaptive", 1e6 / a_tps,
                 f"tps={a_tps:.1f};mean_accepted={a_acc:.3f};"
                 f"switches={eng.stats['tree_switches']};"
                 f"hist={'/'.join(map(str, hist))}"))
    record["adaptive"] = dict(
        tokens_per_sec=round(a_tps, 2), mean_accepted=round(a_acc, 4),
        bank=[list(t.branching) for t in bank.templates],
        live_steps_per_template=hist,
        switches=int(eng.stats["tree_switches"]))
    assert a_acc >= s_acc, (
        f"adaptive tree mean accepted fell below the static (2,2,2,1) "
        f"baseline ({a_acc:.3f} < {s_acc:.3f})")
    # the controller's host path (vectorized EWMA update + cached template
    # scoring) must not tax the step loop: adaptive tok/s stays within 5%
    # of the static baseline at >= its acceptance
    assert a_tps >= 0.95 * s_tps, (
        f"adaptive tree tok/s fell below 0.95x the static baseline "
        f"({a_tps:.1f} < 0.95 * {s_tps:.1f}) — controller host overhead "
        f"is back in the step loop")
    record["gate"] = dict(
        adaptive_vs_static_tps=round(a_tps / s_tps, 4),
        adaptive_tps=round(a_tps, 2), static_tps=round(s_tps, 2))
    common.update_bench_serve("tree_adaptive", record)
    emit(rows, "serve_adaptive", persist=False)
    return rows


def serve_sched(prefix_share: int = 8) -> List:
    """Layered scheduler stack on a shared-prefix workload (DESIGN.md §8):
    ``prefix_share`` requests per distinct 32-token system prompt, each with
    a unique tail, through the paged engine with chunked prefill — once
    cold (prefix_cache=False) and once with the refcounted prefix cache.

    Records tokens/sec, prefix hit rate, TTFT p50/p95 and per-token p50/p95
    latency into BENCH_serve.json's "serve_sched" section (the CI gate
    checks the cached hit rate and that TTFT is reported). Asserts the
    acceptance criteria that are deterministic: cached completions are
    token-identical to cold ones, the steady-state hit rate clears 50%,
    and cached throughput does not regress the no-cache path beyond timer
    noise."""
    tp, tc = load_model("tiny-target")
    dp, dc = load_model("tiny-draft")
    rng = np.random.default_rng(0)
    n_req, sys_len, tail, max_new = 16, 32, 6, 16
    share = max(1, prefix_share)
    n_groups = -(-n_req // share)

    def workload():
        sys_prompts = [np.asarray(common.corpus().prompts(rng, 1,
                                                          sys_len)[0])
                       for _ in range(n_groups)]
        return [np.concatenate([sys_prompts[i % n_groups],
                                rng.integers(0, tc.vocab_size, size=tail)
                                .astype(np.int32)])
                for i in range(n_req)]

    # share > 1: requests rotate through n_groups shared system prompts,
    # identical in both passes (steady-state serving); share == 1: the
    # timed pass gets FRESH prompts, so the cached engine measures a
    # genuinely reuse-free workload — both engines see the same requests
    warm_reqs = workload()
    timed_reqs = warm_reqs if share > 1 else workload()

    def run_engine(cache):
        eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                     max_len=512, kv_block_size=16,
                     prefix_cache=cache)
        for r in warm_reqs:                     # warm pass: compile + (for
            eng.submit(r, max_new)              # the cached engine) prime
        eng.run()
        reqs = timed_reqs
        first_hit = eng.prefix_hit_rate()
        eng.sched.completions.clear()
        eng.stats.update(accepted=0, live_steps=0, prefill_chunks=0,
                         prefix_lookup_blocks=0, prefix_hit_blocks=0)
        for r in reqs:
            eng.submit(r, max_new)
        t0 = time.perf_counter()
        comps = eng.run()
        wall = time.perf_counter() - t0
        toks = {c.rid: c.tokens for c in comps}
        tps = sum(c.generated for c in comps) / wall
        return dict(tps=tps, toks=toks, first_hit=first_hit,
                    hit=eng.prefix_hit_rate(), lat=eng.latency_summary(),
                    acc=eng.mean_accepted())

    rows, record = [], {}
    res = {False: run_engine(False), True: run_engine(True)}
    for cache, r in res.items():
        name = "cached" if cache else "cold"
        lat = r["lat"]
        rows.append((f"serve_sched.{name}", 1e6 / r["tps"],
                     f"tps={r['tps']:.1f};hit={r['hit']:.2f};"
                     f"ttft_p50_ms={lat['ttft_p50_ms']:.1f};"
                     f"ttft_p95_ms={lat['ttft_p95_ms']:.1f};"
                     f"tok_p50_ms={lat['tok_p50_ms']:.2f}"))
        record[name] = dict(
            tokens_per_sec=round(r["tps"], 2),
            prefix_hit_rate=round(r["hit"], 4),
            first_pass_hit_rate=round(r["first_hit"], 4),
            prefix_share=prefix_share,
            mean_accepted=round(r["acc"], 4),
            ttft_p50_ms=round(lat["ttft_p50_ms"], 3),
            ttft_p95_ms=round(lat["ttft_p95_ms"], 3),
            tok_p50_ms=round(lat["tok_p50_ms"], 4),
            tok_p95_ms=round(lat["tok_p95_ms"], 4),
            queue_wait_p50_ms=round(lat["queue_wait_p50_ms"], 3))
    cold, cached = res[False], res[True]
    # deterministic greedy: the cache must be invisible in the tokens
    same = (set(cold["toks"]) == set(cached["toks"]) and
            all(np.array_equal(cold["toks"][r], cached["toks"][r])
                for r in cold["toks"]))
    assert same, "prefix-cached completions diverged from the cold path"
    record["cached"]["token_identical_to_cold"] = True
    if prefix_share > 1:
        assert cached["hit"] >= 0.5, (
            f"shared-prefix workload hit rate {cached['hit']:.2f} < 0.5")
        assert cached["tps"] >= 0.9 * cold["tps"], (
            f"prefix cache slowed serving: {cached['tps']:.1f} vs "
            f"{cold['tps']:.1f} tok/s")
    common.update_bench_serve("serve_sched", record)
    emit(rows, "serve_sched", persist=False)
    return rows


def serve_pipelined() -> List:
    """Overlap-pipelined serve loop (DESIGN.md §9): the same ragged
    self-draft workload through the paged engine, flat-K and the static
    (1,2) tree — the template the sweep picked for raw tok/s: its 4-wide
    draft/verify windows undercut flat's 8/5-wide ones at near-equal
    acceptance — each driven synchronously (depth-1) and pipelined
    (depth-2 dispatch/harvest overlap, donated buffers, one batched
    transfer per step). Asserts — in the benchmark itself, per the
    acceptance criteria — that pipelined completions are byte-identical
    to the synchronous ones for BOTH drafting shapes, and records tok/s,
    steps/sec and host-overhead p50/p95 per config under
    BENCH_serve.json's "serve_pipelined" section. The ROADMAP gate (tree
    beats flat-K in tokens/sec once host overhead is hidden) is encoded
    as the recorded ``gate.tree_pipelined_vs_flat_sync`` ratio, floored
    by ``benchmarks.run --pipelined --smoke-floor 1.0`` in CI."""
    from repro.core.spec_decode import TreeTemplate
    tp, tc = load_model("tiny-target")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=6)]
    max_len, max_new, reps = 512, 96, 3

    def run_engine(tree, pipelined):
        eng = Engine(tp, tc, tp, tc, mode="pard", k=TREE_K, max_batch=2,
                     max_len=max_len, kv_block_size=64,
                     tree=tree)
        for r in reqs:                          # warm pass: compile steps
            eng.submit(r, max_new)
        eng.run(pipelined=pipelined)
        # median-of-reps timing: single passes on a busy CI box are too
        # noisy for a >= 1.0 ratio gate between near-equal configs
        tps_reps, sps_reps = [], []
        toks = None
        for _ in range(reps):
            eng.stats.update(accepted=0, live_steps=0)
            eng.sched.host_overhead_ms.clear()  # timed-pass overhead only
            steps0 = eng.stats["steps"]
            for r in reqs:
                eng.submit(r, max_new)
            t0 = time.perf_counter()
            comps = eng.run(pipelined=pipelined)
            wall = time.perf_counter() - t0
            toks = {c.rid: c.tokens for c in comps[-len(reqs):]}
            tps_reps.append(
                sum(c.generated for c in comps[-len(reqs):]) / wall)
            sps_reps.append((eng.stats["steps"] - steps0) / wall)
        lat = eng.latency_summary()
        return dict(toks=toks, tps=float(np.median(tps_reps)),
                    sps=float(np.median(sps_reps)), acc=eng.mean_accepted(),
                    oh50=lat["host_overhead_p50_ms"],
                    oh95=lat["host_overhead_p95_ms"])

    rows, record = [], {}
    res = {}
    for shape, tree in (("flat", None),
                        ("tree-1x2",
                         TreeTemplate.from_branching((1, 2)))):
        for pipelined in (False, True):
            loop = "pipelined" if pipelined else "sync"
            r = res[shape, pipelined] = run_engine(tree, pipelined)
            rows.append((
                f"serve_pipelined.{shape}.{loop}", 1e6 / r["tps"],
                f"tps={r['tps']:.1f};steps_per_sec={r['sps']:.1f};"
                f"host_oh_p50_ms={r['oh50']:.2f};"
                f"host_oh_p95_ms={r['oh95']:.2f}"))
            record[f"{shape}.{loop}"] = dict(
                tokens_per_sec=round(r["tps"], 2),
                steps_per_sec=round(r["sps"], 2),
                mean_accepted=round(r["acc"], 4),
                host_overhead_p50_ms=round(r["oh50"], 3),
                host_overhead_p95_ms=round(r["oh95"], 3))
        # greedy determinism: the pipeline must be invisible in the tokens
        sync_t, pipe_t = res[shape, False]["toks"], res[shape, True]["toks"]
        same = (set(sync_t) == set(pipe_t) and
                all(np.array_equal(sync_t[r], pipe_t[r]) for r in sync_t))
        assert same, (f"{shape}: pipelined completions diverged from the "
                      f"synchronous loop")
        record[f"{shape}.pipelined"]["token_identical_to_sync"] = True
    ratio = res["tree-1x2", True]["tps"] / res["flat", False]["tps"]
    record["gate"] = dict(
        tree_pipelined_vs_flat_sync=round(ratio, 4),
        tree_pipelined_tps=round(res["tree-1x2", True]["tps"], 2),
        flat_sync_tps=round(res["flat", False]["tps"], 2))
    common.update_bench_serve("serve_pipelined", record)
    emit(rows, "serve_pipelined", persist=False)
    return rows


def serve_kv_quant() -> List:
    """Quantized paged KV (DESIGN.md §10): the same ragged PARD workload
    through the paged engine under the fp32 reference cache and the int8 /
    fp8 quantized caches (per-(position, head) scales stored beside the
    pool, dequantized inside the streaming kernels). Records tokens/sec,
    pool capacity bytes (scales included — the ratio is the honest one) and
    mean accepted length per dtype under BENCH_serve.json's "kv_quant"
    section, plus a ``gate`` entry with the two CI ratios:

      * ``int8_byte_reduction_vs_fp32`` — pool bytes fp32/int8, floored at
        2.0 by ``benchmarks.run --kv-quant --smoke-floor 2.0`` (the
        acceptance criterion; measured ~3.5x: 4-byte values -> 1-byte
        values + one f32 scale per 128-value (block, head) row);
      * ``int8_vs_fp32_tps`` — int8/fp32 tokens/sec, floored at 0.95 (the
        dequant-in-kernel overhead must not eat the win).

    Greedy int8 decoding is self-consistent (spec == AR within the dtype,
    asserted by tests/test_kv_quant.py); here the benchmark additionally
    asserts the int8 run commits full-length completions for every
    request, so a quantization bug that stalls acceptance cannot record a
    plausible-looking tok/s."""
    tp, tc = load_model("tiny-target")
    dp, dc = load_model("tiny-draft")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=6)]
    max_len, max_new, reps = 512, 48, 3

    rows, record = [], {}
    for dtype in ("fp32", "int8", "fp8"):
        eng = Engine(tp, tc, dp, dc, mode="pard", k=4, max_batch=2,
                     max_len=max_len, kv_block_size=64,
                     kv_dtype=dtype)
        for r in reqs:                          # warm pass: compile steps
            eng.submit(r, max_new)
        eng.run()
        eng.peak_kv_bytes_in_use = eng.kv_bytes_in_use()
        # median-of-reps timing: the >= 0.95 tok/s ratio gate compares
        # near-equal configs, too tight for single passes on a busy CI box
        tps_reps, comps = [], []
        for _ in range(reps):
            eng.stats.update(accepted=0, live_steps=0)
            for r in reqs:
                eng.submit(r, max_new)
            t0 = time.perf_counter()
            comps = eng.run()
            wall = time.perf_counter() - t0
            tps_reps.append(
                sum(c.generated for c in comps[-len(reqs):]) / wall)
        tps = float(np.median(tps_reps))
        cap = eng.kv_capacity_bytes()
        peak = eng.peak_kv_bytes_in_use
        acc = eng.mean_accepted()
        assert all(c.generated == max_new for c in comps[-len(reqs):]), \
            f"{dtype}: short completions — acceptance stalled"
        rows.append((f"serve_kv_quant.{dtype}", 1e6 / tps,
                     f"tps={tps:.1f};kv_capacity_mb={cap / 1e6:.2f};"
                     f"kv_peak_mb={peak / 1e6:.2f};mean_acc={acc:.2f}"))
        record[dtype] = dict(
            tokens_per_sec=round(tps, 2), kv_capacity_bytes=cap,
            kv_peak_bytes_in_use=peak, mean_accepted=round(acc, 4))
    record["gate"] = dict(
        int8_byte_reduction_vs_fp32=round(
            record["fp32"]["kv_capacity_bytes"]
            / record["int8"]["kv_capacity_bytes"], 4),
        fp8_byte_reduction_vs_fp32=round(
            record["fp32"]["kv_capacity_bytes"]
            / record["fp8"]["kv_capacity_bytes"], 4),
        int8_vs_fp32_tps=round(
            record["int8"]["tokens_per_sec"]
            / record["fp32"]["tokens_per_sec"], 4))
    common.update_bench_serve("kv_quant", record)
    emit(rows, "serve_kv_quant", persist=False)
    return rows


def serve_sharded() -> List:
    """Tensor-parallel serving on a host device mesh (DESIGN.md §11): the
    same ragged mixed greedy + seeded-sampled PARD workload through the
    paged engine on ("data", "model") submeshes of 1, 2 and 4 forced host
    devices (run under XLA_FLAGS=--xla_force_host_platform_device_count=4;
    ``ensure_host_devices`` raises if the backend came up short). The
    serving ruleset shards only projection OUTPUT dims and all-gathers
    activations before every contraction, so the benchmark asserts — per
    the acceptance criteria — that completions are bitwise-identical
    across all three mesh shapes, then records tokens/sec, tokens/sec per
    chip and the scaling efficiency (per-chip throughput relative to the
    1-device mesh) under BENCH_serve.json's "serve_sharded" section. On
    the forced-CPU mesh the collectives are emulated through host memory,
    so efficiency is a smoke floor (``--scenario sharded --smoke-floor``),
    not a hardware claim — the honest per-chip numbers come from a real
    multi-chip mesh.

    The THROUGHPUT ruleset (DESIGN.md §13) then reruns tp1/tp2/tp4 with
    row-parallel down-projections at canonical-chunk granularity. Its
    measurable gate is not wall-clock but the collective-accounting audit
    (tools/comm_audit.py): the gate-bearing numbers come from
    ``audit_forward`` (params as explicit sharded jit arguments, scan-body
    collectives scaled by trip count — the per-step bill a deployment with
    resident sharded weights pays), with the fused-step ``audit_engine``
    recorded alongside as a diagnostic (closure-constant params let XLA
    fold exact's gathers there). The gate block carries the
    exact/throughput forward byte ratio, the throughput
    all-reduces-per-layer bound, the greedy exact-match rate of
    throughput-tp4 vs the throughput-tp1 reference (the canonical-chunk
    numerics make every mesh size round the same f32 sum once, so this is
    1.0 in practice; vs the EXACT ruleset the throughput numerics differ
    by design and only the mean-accepted drift is bounded), all enforced
    by ``benchmarks.run --scenario sharded --smoke-floor`` in the
    shard-gate CI job."""
    from repro.launch import mesh as mesh_mod
    from repro.serving.config import EngineConfig, SamplingParams
    from tools import comm_audit

    mesh_mod.ensure_host_devices(4)
    tgt, tc = load_model("tiny-target")
    dp, dc = load_model("tiny-draft")
    rng = np.random.default_rng(0)
    reqs = [np.asarray(common.corpus().prompts(rng, 1, int(n_tok))[0])
            for n_tok in rng.integers(8, 24, size=6)]
    max_len, max_new, reps = 512, 48, 3

    def run_engine(n, ruleset="exact", audit=False):
        cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=max_len,
                           kv_block_size=64, seed=3,
                           mesh=mesh_mod.make_host_mesh(model=n, data=1),
                           tp_ruleset=ruleset)
        eng = Engine(tgt, tc, dp, dc, config=cfg)

        def submit_all():
            # mixed batch: even requests greedy, odd ones sampled with
            # per-request pinned seeds (identity must hold for both paths)
            ids = set()
            for i, r in enumerate(reqs):
                rid = eng.submit(r, params=SamplingParams(
                    max_new=max_new,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    seed=None if i % 2 == 0 else 100 + i))
                if i % 2 == 0:
                    ids.add(rid)
            return ids

        submit_all()                            # warm pass: compile steps
        eng.run()
        tps_reps, toks, greedy = [], None, set()
        for _ in range(reps):
            eng.stats.update(accepted=0, live_steps=0)
            greedy = submit_all()
            t0 = time.perf_counter()
            comps = eng.run()
            wall = time.perf_counter() - t0
            toks = {c.rid: c.tokens for c in comps[-len(reqs):]}
            tps_reps.append(
                sum(c.generated for c in comps[-len(reqs):]) / wall)
        out = dict(toks=toks, tps=float(np.median(tps_reps)),
                   acc=eng.mean_accepted(), greedy=greedy)
        if audit:
            out["comm"] = comm_audit.audit_engine(eng)
        return out

    def greedy_match_rate(base, other):
        """Position-wise token agreement over the GREEDY completions of the
        final timed pass (rids align: identical submission sequences)."""
        match = total = 0
        for rid in sorted(other["greedy"]):
            a = np.asarray(base["toks"][rid])
            b = np.asarray(other["toks"][rid])
            m = min(len(a), len(b))
            match += int(np.sum(a[:m] == b[:m]))
            total += max(len(a), len(b))
        return match / max(1, total)

    rows, record, res = [], {"config": _recording_config()}, {}
    for n in (1, 2, 4):
        r = res[n] = run_engine(n, audit=(n == 4))
        eff = (r["tps"] / n) / res[1]["tps"]
        rows.append((f"serve_sharded.tp{n}", 1e6 / r["tps"],
                     f"tps={r['tps']:.1f};tps_per_chip={r['tps'] / n:.1f};"
                     f"scaling_eff={eff:.3f};mean_acc={r['acc']:.2f}"))
        record[f"tp{n}"] = dict(
            tokens_per_sec=round(r["tps"], 2),
            tokens_per_sec_per_chip=round(r["tps"] / n, 2),
            scaling_efficiency=round(eff, 4),
            mean_accepted=round(r["acc"], 4))
        if n > 1:
            base = res[1]["toks"]
            same = (set(base) == set(r["toks"]) and
                    all(np.array_equal(base[rid], r["toks"][rid])
                        for rid in base))
            assert same, (f"tp={n}: completions diverged from the 1-device "
                          f"mesh — sharding leaked into the tokens")
            record[f"tp{n}"]["token_identical_to_tp1"] = True

    thr = {n: run_engine(n, ruleset="throughput", audit=(n == 4))
           for n in (1, 2, 4)}
    for n, r in thr.items():
        m = greedy_match_rate(thr[1], r)          # vs the thr-tp1 reference
        m_exact = greedy_match_rate(res[1], r)    # vs exact-tp1 (diagnostic)
        eff = (r["tps"] / n) / res[1]["tps"]
        rows.append((f"serve_sharded.tp{n}.throughput", 1e6 / r["tps"],
                     f"tps={r['tps']:.1f};scaling_eff={eff:.3f};"
                     f"greedy_match={m:.4f};mean_acc={r['acc']:.2f}"))
        record[f"tp{n}.throughput"] = dict(
            tokens_per_sec=round(r["tps"], 2),
            tokens_per_sec_per_chip=round(r["tps"] / n, 2),
            scaling_efficiency=round(eff, 4),
            mean_accepted=round(r["acc"], 4),
            greedy_exact_match_rate_vs_throughput_tp1=round(m, 4),
            greedy_exact_match_rate_vs_exact_tp1=round(m_exact, 4))

    # gate-bearing forward audits (params as explicit sharded arguments,
    # scan trip count applied) + the fused-step audits as diagnostics
    mesh4 = mesh_mod.make_host_mesh(model=4, data=1)
    fwd = {rs: comm_audit.audit_forward(tgt, tc, mesh4, rs)
           for rs in ("exact", "throughput")}
    record["comm_audit"] = {
        "forward_exact_tp4": fwd["exact"],
        "forward_throughput_tp4": fwd["throughput"],
        "fused_step_exact_tp4": {k: res[4]["comm"][k]
                                 for k in ("counts", "bytes", "total_count",
                                           "total_bytes", "n_layers",
                                           "all_reduces_per_layer")},
        "fused_step_throughput_tp4": {
            k: thr[4]["comm"][k]
            for k in ("counts", "bytes", "total_count", "total_bytes",
                      "n_layers", "all_reduces_per_layer")},
    }
    exact_b = fwd["exact"]["total_bytes"]
    thr_b = fwd["throughput"]["total_bytes"]
    record["gate"] = dict(
        token_identical_across_meshes=True,
        scaling_efficiency_tp4=record["tp4"]["scaling_efficiency"],
        tp1_tps=record["tp1"]["tokens_per_sec"],
        tp4_tps=record["tp4"]["tokens_per_sec"],
        comm_bytes_exact_tp4=exact_b,
        comm_bytes_throughput_tp4=thr_b,
        comm_bytes_ratio_exact_vs_throughput_tp4=round(
            exact_b / max(1, thr_b), 4),
        all_reduces_per_layer_throughput_tp4=fwd["throughput"][
            "all_reduces_per_layer"],
        throughput_tp4_tps=round(thr[4]["tps"], 2),
        throughput_tp4_greedy_exact_match_rate=round(
            greedy_match_rate(thr[1], thr[4]), 4),
        throughput_mean_accepted_rel_delta=round(
            (thr[4]["acc"] - res[4]["acc"]) / res[4]["acc"], 4))
    common.update_bench_serve("serve_sharded", record)
    emit(rows, "serve_sharded", persist=False)
    return rows


def serve_dp() -> List:
    """Data-parallel engine replicas behind one scheduler (DESIGN.md §12):
    the same saturated shared-prefix mixed greedy + seeded-sampled PARD
    workload through dp=1 and dp=2 paged prefix-cached engines on 4 forced
    host devices. Asserts — per the acceptance criteria — that dp=2
    commits the IDENTICAL token set as dp=1 for the same request set
    (routing can never change tokens: greedy decoding is deterministic
    and sampled rows derive their PRNG streams from (seed, rid),
    independent of replica/slot/batch composition), then records
    aggregate tokens/sec for both, their ratio, and the warm
    cross-replica prefix hit rate under BENCH_serve.json's "serve_dp"
    section. On a single-core CPU host the two replicas' device work
    serializes, so the dp-gate's throughput floor is deliberately loose
    (like shard-gate's) — the >= 1.5x aggregate-throughput expectation is
    a statement about parallel-capable runners / real accelerators, and
    the measured ratio is recorded honestly either way; the token-set
    identity half of the gate is exact everywhere."""
    from repro.launch import mesh as mesh_mod
    from repro.serving.config import EngineConfig, SamplingParams

    mesh_mod.ensure_host_devices(4)
    tgt, tc = load_model("tiny-target")
    dpar, dc = load_model("tiny-draft")
    rng = np.random.default_rng(0)
    # saturated queue: 12 requests through 2 slots per replica, 3 distinct
    # 64-token system prompts (each exactly one KV block) with unique
    # 8-token tails — the warm pass seeds each prefix into some replica's
    # pool, the timed passes route same-prefix requests back to its owner
    sys_p = [np.asarray(common.corpus().prompts(rng, 1, 64)[0], np.int32)
             for _ in range(3)]
    reqs = [np.concatenate([
        sys_p[i % 3],
        np.asarray(common.corpus().prompts(rng, 1, 8)[0], np.int32)])
        for i in range(12)]
    max_new, reps = 32, 3

    def run_engine(n):
        cfg = EngineConfig(mode="pard", k=4, max_batch=2, max_len=512,
                           kv_block_size=64, seed=3,
                           prefix_cache=True, pipelined=True, dp=n)
        eng = Engine(tgt, tc, dpar, dc, config=cfg)

        def submit_all():
            # mixed batch: even requests greedy, odd ones sampled with
            # per-request pinned seeds (identity must hold for both paths)
            for i, r in enumerate(reqs):
                eng.submit(r, params=SamplingParams(
                    max_new=max_new,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    seed=None if i % 2 == 0 else 100 + i))

        submit_all()        # warm pass: compile steps + seed the prefixes
        eng.run()
        eng.stats.update(accepted=0, live_steps=0, affinity_routed=0,
                         prefix_lookup_blocks=0, prefix_hit_blocks=0)
        tps_reps, toks = [], None
        for _ in range(reps):
            submit_all()
            t0 = time.perf_counter()
            comps = eng.run()
            wall = time.perf_counter() - t0
            toks = {c.rid: c.tokens for c in comps[-len(reqs):]}
            tps_reps.append(
                sum(c.generated for c in comps[-len(reqs):]) / wall)
        return dict(toks=toks, tps=float(np.median(tps_reps)),
                    acc=eng.mean_accepted(), hit=eng.prefix_hit_rate(),
                    affinity=int(eng.stats["affinity_routed"]),
                    rep_steps=[int(s)
                               for s in eng.stats["replica_steps"]])

    res = {n: run_engine(n) for n in (1, 2)}
    base, other = res[1]["toks"], res[2]["toks"]
    same = (set(base) == set(other) and
            all(np.array_equal(base[rid], other[rid]) for rid in base))
    assert same, ("dp=2 completions diverged from dp=1 — replica routing "
                  "leaked into the tokens")
    ratio = res[2]["tps"] / res[1]["tps"]
    rows, record = [], {}
    for n in (1, 2):
        r = res[n]
        rows.append((f"serve_dp.dp{n}", 1e6 / r["tps"],
                     f"tps={r['tps']:.1f};warm_hit={r['hit']:.3f};"
                     f"mean_acc={r['acc']:.2f}"))
        record[f"dp{n}"] = dict(
            tokens_per_sec=round(r["tps"], 2),
            warm_prefix_hit_rate=round(r["hit"], 4),
            mean_accepted=round(r["acc"], 4),
            affinity_routed=r["affinity"],
            replica_steps=r["rep_steps"])
    record["dp2"]["token_identical_to_dp1"] = True
    record["gate"] = dict(
        token_set_identical=True,
        aggregate_tps_ratio_dp2_vs_dp1=round(ratio, 4),
        warm_cross_replica_prefix_hit_rate=round(res[2]["hit"], 4),
        dp1_tps=record["dp1"]["tokens_per_sec"],
        dp2_tps=record["dp2"]["tokens_per_sec"])
    common.update_bench_serve("serve_dp", record)
    emit(rows, "serve_dp", persist=False)
    return rows


ALL = {"table1": table1, "table2": table2, "table3": table3,
       "table4": table4, "table5": table5, "table6": table6,
       "fig6a": fig6a, "fig6b": fig6b, "serve": serve,
       "serve_tree": serve_tree, "serve_adaptive": serve_adaptive,
       "serve_sched": serve_sched, "serve_pipelined": serve_pipelined,
       "serve_kv_quant": serve_kv_quant, "serve_sharded": serve_sharded,
       "serve_dp": serve_dp}
