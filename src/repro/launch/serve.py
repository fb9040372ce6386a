"""Serving launcher: stand up the batched engine and stream synthetic
requests through it.

  PYTHONPATH=src python -m repro.launch.serve --target tiny-target \
      --draft tiny-draft --mode pard --requests 16 --max-new 48 \
      [--target-ckpt a.npz --draft-ckpt b.npz] [--tp 2 --devices 4]

Engine construction goes through the typed ``EngineConfig`` surface
(``EngineConfig.from_args``) and per-request options through
``SamplingParams`` — this launcher doubles as the usage example for both.
``--tp N`` serves tensor-parallel over a (data=1, model=N) mesh
(DESIGN.md §11); ``--dp N`` serves N data-parallel engine replicas behind
one scheduler (DESIGN.md §12); on a CPU-only host pair either with
``--devices M`` to force M >= tp * dp host devices.

Prints per-request latency and aggregate tokens/s — the same metrics as the
paper's Tables 1-4 (benchmarks/ runs this machinery systematically).
"""
import argparse
import time


def load_params(cfg, key, ckpt=None, *, mesh=None, tp_ruleset="exact",
                replicate=False):
    """Random params for ``cfg`` drawn from ``key``, then the checkpoint at
    ``ckpt`` if given.

    Without a mesh they land on the default device. With one,
    ``init_params`` runs under ``jit`` with ``out_shardings`` set to the
    serving layout — the target ruleset's (``param_specs(serving=True)``)
    or, for a draft (``replicate=True``), a full copy per device — so each
    device only ever materialises its own shard: a target larger than one
    chip is never held whole on one (DESIGN.md §11)."""
    import functools

    import jax

    from repro.models import init_params
    from repro.sharding import specs

    if mesh is None:
        params = init_params(key, cfg)
    else:
        init = functools.partial(init_params, cfg=cfg)
        shapes = jax.eval_shape(init, key)
        tree = (specs.replicated_specs(shapes) if replicate else
                specs.param_specs(shapes, mesh, serving=True,
                                  ruleset=tp_ruleset))
        params = jax.jit(init, out_shardings=specs.to_named(tree, mesh))(key)
    if ckpt:
        from repro.training import checkpoint
        params = checkpoint.restore(ckpt, params)
    return params


def make_prompts(vocab_size, n, prompt_len, seed, *, share=1):
    """``n`` synthetic prompts from the Markov corpus. ``share > 1`` is
    the shared-prefix mix: ``share`` requests per system prompt of
    ``prompt_len`` tokens, each with a unique 8-token tail; groups
    interleave round-robin so same-prefix requests arrive across batch
    generations — concurrent identical prompts cannot hit (computed
    gating), later arrivals do."""
    import numpy as np

    from repro.data.pipeline import MarkovCorpus

    corpus = MarkovCorpus(vocab_size=vocab_size, seed=0, determinism=2.0)
    rng = np.random.default_rng(seed)
    share = max(1, share)
    sys_prompts = [corpus.prompts(rng, 1, prompt_len)[0]
                   for _ in range(-(-n // share))]
    if share == 1:
        return [corpus.prompts(rng, 1, prompt_len)[0] for _ in range(n)]
    return [np.concatenate([sys_prompts[i % len(sys_prompts)],
                            np.asarray(corpus.prompts(rng, 1, 8)[0],
                                       np.int32)])
            for i in range(n)]


def serve_requests(eng, prompts, max_new, *, greedy_requests=0):
    """Submit ``prompts`` and run the engine to completion; returns the
    completions and the wall seconds from first submit to last token.
    The first ``greedy_requests`` requests decode greedily even when the
    engine default samples (mixed batches)."""
    from repro.serving.engine import SamplingParams

    t0 = time.perf_counter()
    for i, prompt in enumerate(prompts):
        temp = 0.0 if i < greedy_requests else None
        eng.submit(prompt, params=SamplingParams(max_new=max_new,
                                                 temperature=temp))
    comps = eng.run()                # pipelining comes from config.pipelined
    return comps, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", required=True)
    ap.add_argument("--draft", default=None)
    ap.add_argument("--target-ckpt", default=None)
    ap.add_argument("--draft-ckpt", default=None)
    ap.add_argument("--mode", default="pard", choices=["ar", "vsd", "pard"])
    ap.add_argument("--tree", default=None, metavar="B1,B2,...",
                    help="tree-structured PARD drafting: per-depth branching "
                         "factors of the candidate tree (e.g. 2,2,2,1); "
                         "overrides --k with the tree depth")
    ap.add_argument("--adaptive-tree", action="store_true",
                    help="per-request tree templates from the default "
                         "chain/balanced/wide bank at depth --k, re-selected "
                         "from EWMA acceptance statistics at admission and "
                         "between windows (DESIGN.md §7); excludes --tree")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy); works with "
                         "--tree via multi-round sibling acceptance")
    ap.add_argument("--greedy-requests", type=int, default=0, metavar="N",
                    help="submit the first N requests with temperature 0 "
                         "(the rest use --temperature): one batch mixes "
                         "greedy and sampled rows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-block-size", type=int, default=64)
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "fp32", "int8", "fp8"],
                    help="KV cache storage dtype; int8/fp8 quantize on "
                         "append with per-(position, head) scales and "
                         "dequantize inside the attention kernels "
                         "(DESIGN.md §10)")
    ap.add_argument("--kv-num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: worst-case "
                         "coverage)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prompt-prefix reuse in the KV pool "
                         "(DESIGN.md §8): same-prefix requests map cached "
                         "blocks copy-free and only prefill their tails")
    ap.add_argument("--prefix-share", type=int, default=1, metavar="N",
                    help="workload mix: requests per distinct system "
                         "prompt (1 = every prompt unique; pair with "
                         "--prefix-cache to see hits)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    metavar="TOKENS",
                    help="max prompt tokens consumed per step across "
                         "prefilling rows (chunked-prefill lanes; default "
                         "unthrottled)")
    ap.add_argument("--pipelined", action="store_true",
                    help="two-deep dispatch/harvest pipeline: step t+1 is "
                         "dispatched while step t is in flight (DESIGN.md "
                         "§9); token-identical to the synchronous loop")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel serving over a (data=1, model=N) "
                         "device mesh: target params + KV heads shard, the "
                         "draft replicates; tokens are identical to --tp 1 "
                         "(DESIGN.md §11)")
    ap.add_argument("--dp", type=int, default=1, metavar="N",
                    help="data-parallel serving: N independent engine "
                         "replicas on a (data=N, model=tp) mesh behind one "
                         "scheduler, routed prefix-affinity-then-least-"
                         "loaded; tokens are identical to --dp 1 "
                         "(DESIGN.md §12). --kv-num-blocks is per replica")
    ap.add_argument("--tp-ruleset", default="exact",
                    choices=["exact", "throughput"],
                    help="tensor-parallel sharding ruleset: 'exact' "
                         "(default) is reduction-free — tokens bitwise "
                         "identical across mesh shapes (DESIGN.md §11); "
                         "'throughput' is Megatron-style row-parallel "
                         "down-projections — one psum per attention block "
                         "/ MLP, tokens match tp1 to tolerance only "
                         "(DESIGN.md §13)")
    ap.add_argument("--devices", type=int, default=None, metavar="M",
                    help="force M host (CPU) devices before jax initializes "
                         "— development/CI stand-in for real accelerators; "
                         "must be >= --tp * --dp")
    args = ap.parse_args()

    if args.devices:
        # must run before anything touches the jax backend
        from repro.launch.mesh import ensure_host_devices
        ensure_host_devices(args.devices)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import Engine, EngineConfig

    enable_compile_cache()
    config = EngineConfig.from_args(args)
    tree = config.tree
    # a dp > 1 engine places one copy per replica itself (SpecDecoder)
    mesh = config.mesh if config.dp == 1 else None
    tc = get_config(args.target)
    tp = load_params(tc, jax.random.PRNGKey(0), args.target_ckpt, mesh=mesh,
                     tp_ruleset=config.tp_ruleset)
    dp = dc = None
    if args.mode != "ar":
        assert args.draft, "--draft required for vsd/pard"
        dc = get_config(args.draft)
        dp = load_params(dc, jax.random.PRNGKey(1), args.draft_ckpt,
                         mesh=mesh, replicate=True)
    eng = Engine(tp, tc, dp, dc, config=config)

    prompts = make_prompts(tc.vocab_size, args.requests, args.prompt_len,
                           args.seed, share=args.prefix_share)
    comps, wall = serve_requests(eng, prompts, args.max_new,
                                 greedy_requests=args.greedy_requests)

    total = sum(c.generated for c in comps)
    label = args.mode if tree is None else (
        f"{args.mode}[adaptive {tree.key}]" if args.adaptive_tree
        else f"{args.mode}[tree {args.tree}]")
    if args.temperature:
        label += f"[T={args.temperature}" + (
            f",greedy×{args.greedy_requests}]" if args.greedy_requests
            else "]")
    if args.pipelined:
        label += "[pipelined]"
    if args.tp > 1:
        label += f"[tp={args.tp}]"
    if args.dp > 1:
        label += f"[dp={args.dp}]"
    print(f"\nmode={label} requests={len(comps)} "
          f"generated={total} tokens wall={wall:.2f}s "
          f"throughput={total / wall:.1f} tok/s "
          f"steps/s={eng.stats['steps'] / wall:.1f} "
          f"mean_accepted={eng.mean_accepted():.2f}")
    lats = sorted(c.wall_done - c.wall_submitted for c in comps)
    lat = eng.latency_summary()
    print(f"latency p50={lats[len(lats) // 2]:.2f}s p max={lats[-1]:.2f}s "
          f"ttft_p50={lat['ttft_p50_ms']:.0f}ms "
          f"ttft_p95={lat['ttft_p95_ms']:.0f}ms "
          f"tok_p50={lat['tok_p50_ms']:.1f}ms "
          f"tok_p95={lat['tok_p95_ms']:.1f}ms")
    print(f"host overhead (harvest->dispatch) "
          f"p50={lat['host_overhead_p50_ms']:.2f}ms "
          f"p95={lat['host_overhead_p95_ms']:.2f}ms")
    print(f"kv block_size={args.kv_block_size} dtype={args.kv_dtype} "
          f"capacity={eng.kv_capacity_bytes() / 1e6:.2f}MB "
          f"peak_in_use={eng.peak_kv_bytes_in_use / 1e6:.2f}MB")
    if args.prefix_cache:
        print(f"prefix cache: hit_rate={eng.prefix_hit_rate():.2f} "
              f"({eng.stats['prefix_hit_blocks']}/"
              f"{eng.stats['prefix_lookup_blocks']} prompt blocks)")
    if args.adaptive_tree:
        hist = eng.stats["tree_hist"]
        per = {t.branching: int(h) for t, h in zip(tree.templates, hist)}
        print(f"adaptive tree: live-steps per template {per} "
              f"switches={eng.stats['tree_switches']}")
    print("engine stats:", eng.stats)


if __name__ == "__main__":
    main()
