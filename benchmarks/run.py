"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only table1,table5] [--list]
  PYTHONPATH=src python -m benchmarks.run --tree [--smoke-floor 1.8]
  PYTHONPATH=src python -m benchmarks.run --tree --temperature 0.8 \
      [--smoke-floor 1.3]
  PYTHONPATH=src python -m benchmarks.run --scenario sched \
      [--prefix-share 8] [--smoke-floor 0.5]

Prints ``name,us_per_call,derived`` CSV. Requires the trained artifacts
(``python examples/pard_adaptation_train.py``); without them it falls back
to random weights and WARNS (timings still valid, acceptance meaningless —
except the serve_tree table, which self-drafts and stays meaningful).

``--tree`` runs the tree-drafting serve benchmark (serve_tree);
``--temperature`` > 0 switches it to sampled (multi-round rejection
sampling) acceptance, recorded under BENCH_serve.json's "tree_sampled"
section. ``--scenario sched`` runs the layered-scheduler benchmark
(serve_sched: shared-prefix workload, ``--prefix-share`` requests per
system prompt, TTFT/per-token latency + prefix hit rate recorded under
"serve_sched"). ``--pipelined`` runs the overlap-pipeline benchmark
(serve_pipelined: sync vs depth-2 loops, byte-identity asserted, tok/s +
steps/sec + host-overhead recorded under "serve_pipelined").
``--smoke-floor`` turns the run into the CI regression gate: it exits
non-zero with a one-line diagnostic naming the failing mode/metric unless
every PARD mean accepted length recorded in the section that this run
wrote ("tree"/"tree_sampled"/...) stays at or above the floor — for
``--scenario sched`` the floor applies to the cached prefix hit rate
instead (TTFT must have been recorded), and for ``--pipelined`` it
applies to the tree-pipelined / flat-synchronous tokens/sec ratio
(normally 1.0: the ROADMAP gate that tree WINS throughput once host
overhead is hidden), and for ``--kv-quant`` it applies to the int8
pool-byte reduction vs fp32 (normally 2.0) with a fixed secondary
0.95x fp32 tokens/sec floor ("kv_quant" section, quant-gate job), and
for ``--scenario sharded`` it applies to the tp=4 per-chip scaling
efficiency (loose on CPU-emulated collectives; token identity across
mesh shapes is always required — "serve_sharded" section, shard-gate
job; run under XLA_FLAGS=--xla_force_host_platform_device_count=4),
and for ``--scenario dp`` it applies to the dp=2 / dp=1 aggregate
tokens/sec ratio (loose on single-core hosts where the replicas'
device work serializes — the >= 1.5x production expectation presumes
parallel-capable runners; token-set identity between dp=2 and dp=1 is
always required — "serve_dp" section, dp-gate job; run under
XLA_FLAGS=--xla_force_host_platform_device_count=4).

The roofline/dry-run numbers (deliverable e/g) are produced separately by
``python -m repro.launch.dryrun --all --both-meshes`` and summarised with
``python -m benchmarks.roofline_report``.
"""
import argparse
import json
import sys
import time


# the secondary kv_quant gate ratio: int8 tokens/sec must stay within 5%
# of fp32 (the primary --smoke-floor applies to the byte-reduction ratio)
KV_QUANT_TPS_FLOOR = 0.95

# fixed secondary gates for --scenario sharded (the comm-audit gates,
# DESIGN.md §13; the primary --smoke-floor stays on scaling efficiency):
# on tp4 the throughput ruleset must cut per-step collective bytes >= 2x
# vs exact, bound all-reduces at <= 2 per layer, match tp1 greedy tokens
# at >= 0.99 exact-match rate, and hold mean_accepted within 2% of exact
COMM_BYTES_RATIO_FLOOR = 2.0
COMM_ALL_REDUCES_PER_LAYER_MAX = 2.0
THROUGHPUT_EXACT_MATCH_FLOOR = 0.99
THROUGHPUT_MEAN_ACCEPTED_TOL = 0.02

# fixed secondary gate for --adaptive-tree: the vectorized controller host
# path must keep adaptive tok/s >= 0.95x the static baseline at >= its
# acceptance (the primary --smoke-floor stays on mean accepted length)
ADAPTIVE_TPS_FLOOR = 0.95


def check_floor(floor: float, section: str = "tree") -> int:
    """CI gate: every recorded PARD mean accepted length in ``section``
    must be >= floor — except ``serve_sched``, where the floor applies to
    the cached prefix hit rate and TTFT must have been recorded. Prints one
    diagnostic line per entry naming the mode and metric; returns a
    process exit code."""
    from . import common

    with open(common.BENCH_SERVE) as f:
        record = json.load(f)
    tree = record.get(section)
    if not tree:
        flag = {"tree": "--tree", "tree_sampled": "--tree --temperature 0.8",
                "tree_adaptive": "--adaptive-tree",
                "serve_sched": "--scenario sched",
                "serve_pipelined": "--pipelined",
                "kv_quant": "--kv-quant",
                "serve_sharded": "--scenario sharded",
                "serve_dp": "--scenario dp"}.get(section, "--tree")
        print(f"smoke-floor: no '{section}' section in {common.BENCH_SERVE}"
              f" — run with {flag}", file=sys.stderr)
        return 2
    failed = False
    if section == "serve_pipelined":
        # the ROADMAP gate: tree-mode pipelined tokens/sec must clear the
        # flat-K synchronous baseline (ratio >= floor, normally 1.0), and
        # byte-identity must have been asserted by the benchmark run
        gate = tree.get("gate", {})
        ratio = gate.get("tree_pipelined_vs_flat_sync")
        ok = ratio is not None and ratio >= floor
        failed |= not ok
        print(f"smoke-floor: serve_pipelined tree-pipelined/flat-sync tok/s"
              f"={ratio if ratio is None else f'{ratio:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {floor} "
              f"(tree_pipelined={gate.get('tree_pipelined_tps')} "
              f"flat_sync={gate.get('flat_sync_tps')})", file=sys.stderr)
        for name, entry in sorted(tree.items()):
            if not name.endswith(".pipelined"):
                continue
            ok = entry.get("token_identical_to_sync") is True
            failed |= not ok
            print(f"smoke-floor: serve_pipelined.{name} "
                  f"token_identical_to_sync="
                  f"{entry.get('token_identical_to_sync')} "
                  f"{'ok' if ok else 'MISSING/FAIL'}", file=sys.stderr)
        return 1 if failed else 0
    if section == "kv_quant":
        # the quantized-KV acceptance gate: int8 paged serving must record
        # >= floor x byte reduction vs fp32 (scales included) AND hold
        # >= KV_QUANT_TPS_FLOOR x the fp32 tokens/sec (dequant-in-kernel
        # must not eat the win); every dtype must have recorded a tok/s
        gate = tree.get("gate", {})
        ratio = gate.get("int8_byte_reduction_vs_fp32")
        ok = ratio is not None and ratio >= floor
        failed |= not ok
        print(f"smoke-floor: kv_quant int8 byte reduction vs fp32="
              f"{ratio if ratio is None else f'{ratio:.3f}'}x "
              f"{'>=' if ok else '< FAIL'} {floor}", file=sys.stderr)
        tps = gate.get("int8_vs_fp32_tps")
        ok = tps is not None and tps >= KV_QUANT_TPS_FLOOR
        failed |= not ok
        print(f"smoke-floor: kv_quant int8/fp32 tok/s="
              f"{tps if tps is None else f'{tps:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {KV_QUANT_TPS_FLOOR}",
              file=sys.stderr)
        for name in ("fp32", "int8", "fp8"):
            ok = tree.get(name, {}).get("tokens_per_sec") is not None
            failed |= not ok
            print(f"smoke-floor: kv_quant.{name} tokens_per_sec="
                  f"{tree.get(name, {}).get('tokens_per_sec')} "
                  f"{'recorded' if ok else 'MISSING'}", file=sys.stderr)
        return 1 if failed else 0
    if section == "serve_sharded":
        # the sharded-serving gate: the benchmark must have asserted
        # bitwise token identity across mesh shapes 1/2/4, and the
        # 4-device per-chip throughput must clear the (loose, CPU-emulated
        # collectives) scaling-efficiency floor; every mesh size must have
        # recorded a tok/s
        gate = tree.get("gate", {})
        ok = gate.get("token_identical_across_meshes") is True
        failed |= not ok
        print(f"smoke-floor: serve_sharded token_identical_across_meshes="
              f"{gate.get('token_identical_across_meshes')} "
              f"{'ok' if ok else 'MISSING/FAIL'}", file=sys.stderr)
        eff = gate.get("scaling_efficiency_tp4")
        ok = eff is not None and eff >= floor
        failed |= not ok
        print(f"smoke-floor: serve_sharded tp4 scaling efficiency="
              f"{eff if eff is None else f'{eff:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {floor} "
              f"(tp1={gate.get('tp1_tps')} tp4={gate.get('tp4_tps')} "
              f"tok/s)", file=sys.stderr)
        for name in ("tp1", "tp2", "tp4"):
            ok = tree.get(name, {}).get("tokens_per_sec") is not None
            failed |= not ok
            print(f"smoke-floor: serve_sharded.{name} tokens_per_sec="
                  f"{tree.get(name, {}).get('tokens_per_sec')} "
                  f"{'recorded' if ok else 'MISSING'}", file=sys.stderr)
        # comm-audit gates (DESIGN.md §13): collective-byte accounting of
        # the compiled step is the trustworthy proxy for real-interconnect
        # cost that CPU-emulated wall-clock is not
        ratio = gate.get("comm_bytes_ratio_exact_vs_throughput_tp4")
        ok = ratio is not None and ratio >= COMM_BYTES_RATIO_FLOOR
        failed |= not ok
        print(f"smoke-floor: serve_sharded comm bytes exact/throughput tp4="
              f"{ratio if ratio is None else f'{ratio:.2f}'}x "
              f"{'>=' if ok else '< FAIL'} {COMM_BYTES_RATIO_FLOOR} "
              f"(exact={gate.get('comm_bytes_exact_tp4')} "
              f"throughput={gate.get('comm_bytes_throughput_tp4')} B/step)",
              file=sys.stderr)
        arpl = gate.get("all_reduces_per_layer_throughput_tp4")
        ok = arpl is not None and arpl <= COMM_ALL_REDUCES_PER_LAYER_MAX
        failed |= not ok
        print(f"smoke-floor: serve_sharded throughput all-reduces/layer="
              f"{arpl} {'<=' if ok else '> FAIL'} "
              f"{COMM_ALL_REDUCES_PER_LAYER_MAX}", file=sys.stderr)
        match = gate.get("throughput_tp4_greedy_exact_match_rate")
        ok = match is not None and match >= THROUGHPUT_EXACT_MATCH_FLOOR
        failed |= not ok
        print(f"smoke-floor: serve_sharded throughput tp4 greedy "
              f"exact-match rate vs tp1="
              f"{match if match is None else f'{match:.4f}'} "
              f"{'>=' if ok else '< FAIL'} {THROUGHPUT_EXACT_MATCH_FLOOR}",
              file=sys.stderr)
        drift = gate.get("throughput_mean_accepted_rel_delta")
        ok = drift is not None and abs(drift) <= THROUGHPUT_MEAN_ACCEPTED_TOL
        failed |= not ok
        print(f"smoke-floor: serve_sharded throughput mean_accepted drift="
              f"{drift if drift is None else f'{drift:+.4f}'} "
              f"{'within' if ok else 'OUTSIDE FAIL'} "
              f"+/-{THROUGHPUT_MEAN_ACCEPTED_TOL}", file=sys.stderr)
        return 1 if failed else 0
    if section == "serve_dp":
        # the data-parallel serving gate: the benchmark must have asserted
        # token-SET identity between dp=2 and dp=1 for the same request
        # set, the dp=2/dp=1 aggregate tok/s ratio must clear the (loose,
        # single-core hosts serialize the replicas) floor, the warm
        # cross-replica prefix hit rate must have been recorded, and both
        # dp sizes must have recorded a tok/s
        gate = tree.get("gate", {})
        ok = gate.get("token_set_identical") is True
        failed |= not ok
        print(f"smoke-floor: serve_dp token_set_identical="
              f"{gate.get('token_set_identical')} "
              f"{'ok' if ok else 'MISSING/FAIL'}", file=sys.stderr)
        ratio = gate.get("aggregate_tps_ratio_dp2_vs_dp1")
        ok = ratio is not None and ratio >= floor
        failed |= not ok
        print(f"smoke-floor: serve_dp dp2/dp1 aggregate tok/s="
              f"{ratio if ratio is None else f'{ratio:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {floor} "
              f"(dp1={gate.get('dp1_tps')} dp2={gate.get('dp2_tps')} "
              f"tok/s)", file=sys.stderr)
        hit = gate.get("warm_cross_replica_prefix_hit_rate")
        ok = hit is not None
        failed |= not ok
        print(f"smoke-floor: serve_dp warm_cross_replica_prefix_hit_rate="
              f"{hit} {'recorded' if ok else 'MISSING'}", file=sys.stderr)
        for name in ("dp1", "dp2"):
            ok = tree.get(name, {}).get("tokens_per_sec") is not None
            failed |= not ok
            print(f"smoke-floor: serve_dp.{name} tokens_per_sec="
                  f"{tree.get(name, {}).get('tokens_per_sec')} "
                  f"{'recorded' if ok else 'MISSING'}", file=sys.stderr)
        return 1 if failed else 0
    if section == "serve_sched":
        hit = tree.get("cached", {}).get("prefix_hit_rate")
        ok = hit is not None and hit >= floor
        failed |= not ok
        print(f"smoke-floor: serve_sched.cached prefix_hit_rate="
              f"{hit if hit is None else f'{hit:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {floor}", file=sys.stderr)
        for name, entry in sorted(tree.items()):
            ok = entry.get("ttft_p50_ms") is not None
            failed |= not ok
            print(f"smoke-floor: serve_sched.{name} ttft_p50_ms="
                  f"{entry.get('ttft_p50_ms')} "
                  f"{'recorded' if ok else 'MISSING'}", file=sys.stderr)
        return 1 if failed else 0
    for name, entry in sorted(tree.items()):
        acc = entry.get("mean_accepted")
        if acc is None:
            continue
        ok = acc >= floor
        failed |= not ok
        print(f"smoke-floor: {section}.{name} mean_accepted={acc:.3f} "
              f"{'>=' if ok else '< FAIL'} {floor}", file=sys.stderr)
    if section == "tree_adaptive":
        # secondary gate: the controller's host path must not tax the step
        # loop — adaptive tok/s >= ADAPTIVE_TPS_FLOOR x static at >= its
        # acceptance (the benchmark run asserts acceptance itself)
        gate = tree.get("gate", {})
        ratio = gate.get("adaptive_vs_static_tps")
        ok = ratio is not None and ratio >= ADAPTIVE_TPS_FLOOR
        failed |= not ok
        print(f"smoke-floor: tree_adaptive adaptive/static tok/s="
              f"{ratio if ratio is None else f'{ratio:.3f}'} "
              f"{'>=' if ok else '< FAIL'} {ADAPTIVE_TPS_FLOOR} "
              f"(adaptive={gate.get('adaptive_tps')} "
              f"static={gate.get('static_tps')})", file=sys.stderr)
    return 1 if failed else 0


def bench_env() -> dict:
    """Provenance metadata for the recording environment — written as the
    top-level "env" block of BENCH_serve.json so cross-run trajectory
    comparisons (serve_delta, the CI summaries) are interpretable."""
    import os
    import re
    import subprocess

    import jax
    import jaxlib

    forced = os.environ.get("REPRO_HOST_DEVICES")
    if not forced:
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      os.environ.get("XLA_FLAGS", ""))
        forced = m.group(1) if m else None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ).stdout.strip() or None
    except OSError:
        sha = None
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "forced_host_devices": int(forced) if forced else None,
        "git_sha": sha,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. table1,fig6b")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--tree", action="store_true",
                    help="run the tree-drafting serve benchmark (serve_tree)")
    ap.add_argument("--adaptive-tree", action="store_true",
                    help="run the adaptive-template serve benchmark "
                         "(serve_adaptive; records the 'tree_adaptive' "
                         "BENCH_serve section and asserts the controller "
                         "matches the static (2,2,2,1) baseline)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="run the quantized-KV serve benchmark "
                         "(serve_kv_quant: fp32 vs int8 vs fp8 paged "
                         "caches, records the 'kv_quant' BENCH_serve "
                         "section; with --smoke-floor F the CI gate "
                         "requires the int8 byte reduction >= F and "
                         "int8 tok/s >= 0.95x fp32)")
    ap.add_argument("--scenario", default=None,
                    choices=["sched", "serve", "tree", "adaptive",
                             "pipelined", "kv-quant", "sharded", "dp"],
                    help="named serving scenario: 'sched' runs the "
                         "scheduler/prefix-cache benchmark (serve_sched, "
                         "records the 'serve_sched' BENCH_serve section); "
                         "'sharded' runs the tensor-parallel mesh benchmark "
                         "(serve_sharded: submeshes of 1/2/4 forced host "
                         "devices, token identity asserted, per-chip "
                         "scaling recorded under 'serve_sharded'); 'dp' "
                         "runs the data-parallel replica benchmark "
                         "(serve_dp: dp=1 vs dp=2 on 4 forced host "
                         "devices, token-set identity asserted, aggregate "
                         "tok/s ratio + warm cross-replica prefix hit "
                         "rate recorded under 'serve_dp'); "
                         "'serve'/'tree'/'adaptive'/'pipelined' alias the "
                         "other serve tables so CI and local runs share one "
                         "entrypoint")
    ap.add_argument("--pipelined", action="store_true",
                    help="run the overlap-pipelined serve benchmark "
                         "(serve_pipelined: sync vs depth-2 dispatch/"
                         "harvest loops, flat and tree; asserts byte-"
                         "identical output and records the 'serve_"
                         "pipelined' BENCH_serve section; with "
                         "--smoke-floor F the CI gate requires tree-"
                         "pipelined tok/s >= F * flat-sync tok/s)")
    ap.add_argument("--prefix-share", type=int, default=8, metavar="N",
                    help="serve_sched workload mix: requests per distinct "
                         "system prompt (1 = all-unique cold workload)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="serve_tree sampling temperature (0 = greedy; > 0 "
                         "records the 'tree_sampled' BENCH_serve section)")
    ap.add_argument("--smoke-floor", type=float, default=None, metavar="ACC",
                    help="after running, fail unless every PARD mean "
                         "accepted length in the BENCH_serve.json section "
                         "this run wrote is >= ACC (the CI perf gate)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    from . import common, tables

    enable_compile_cache()

    if args.list:
        print("\n".join(tables.ALL))
        return
    if not common.has_artifacts():
        print("WARNING: benchmarks/artifacts missing — run "
              "examples/pard_adaptation_train.py first; using random weights",
              file=sys.stderr)

    scenario_table = {"sched": "serve_sched", "serve": "serve",
                      "tree": "serve_tree", "adaptive": "serve_adaptive",
                      "pipelined": "serve_pipelined",
                      "kv-quant": "serve_kv_quant",
                      "sharded": "serve_sharded", "dp": "serve_dp"}
    scoped = args.tree or args.adaptive_tree or args.pipelined \
        or args.kv_quant or args.scenario
    names = args.only.split(",") if args.only else \
        ([] if scoped else list(tables.ALL))
    if args.tree and "serve_tree" not in names:
        names.append("serve_tree")
    if args.adaptive_tree and "serve_adaptive" not in names:
        names.append("serve_adaptive")
    if args.pipelined and "serve_pipelined" not in names:
        names.append("serve_pipelined")
    if args.kv_quant and "serve_kv_quant" not in names:
        names.append("serve_kv_quant")
    if args.scenario and scenario_table[args.scenario] not in names:
        names.append(scenario_table[args.scenario])
    t0 = time.time()
    print("name,us_per_call,derived")
    for name in names:
        try:
            if name == "serve_tree":
                tables.serve_tree(temperature=args.temperature)
            elif name == "serve_sched":
                tables.serve_sched(prefix_share=args.prefix_share)
            else:
                tables.ALL[name]()
        except AssertionError as e:
            if args.smoke_floor is not None:
                # the CI gate wants a one-line diagnostic naming the failing
                # mode/metric, not a bare assert traceback
                print(f"smoke-floor: {name} FAILED: {e}", file=sys.stderr)
                sys.exit(1)
            raise
    print(f"# total wall: {time.time() - t0:.1f}s", file=sys.stderr)
    if names:
        # provenance: stamp the recording environment alongside whatever
        # sections this run (re)wrote
        common.update_bench_serve("env", bench_env())

    if args.smoke_floor is not None:
        if args.scenario == "sched":
            section = "serve_sched"
        elif args.scenario == "sharded":
            section = "serve_sharded"
        elif args.scenario == "dp":
            section = "serve_dp"
        elif args.pipelined or args.scenario == "pipelined":
            section = "serve_pipelined"
        elif args.kv_quant or args.scenario == "kv-quant":
            section = "kv_quant"
        elif args.adaptive_tree:
            section = "tree_adaptive"
        else:
            section = "tree_sampled" if args.temperature > 0 else "tree"
        sys.exit(check_floor(args.smoke_floor, section))


if __name__ == "__main__":
    main()
