"""Chip smoke test: serve a published-width model end to end on a TPU.

  python chip_smoke.py              # one chip: llama3.2-1b
  python chip_smoke.py --chips 4    # four chips: tp=4 and dp=4 serving

One chip runs the main serving path, EngineConfig -> Engine -> Scheduler
-> Executor -> fused jitted step -> Pallas kernels, at the full width of
``llama3.2-1b`` (16 layers, d_model 2048, 32/8 heads, vocab 128256), which
drafts for itself. First the kernels the steps use are checked against
the ``kernels/ref.py`` oracles at that model's head shapes; then four
serving phases run on the paged KV cache, each with 8 requests of 128
prompt tokens and 64 new tokens: AR, PARD flat (k=8), PARD tree
(2,2,2,1), and PARD flat with an int8 KV cache.

Four chips run only what exists across chips: ``llama3.2-1b`` at tp=4
under the exact ruleset against tp=1 — greedy AR served on both, and the
logits of the served forward path, which must agree to bf16 rounding (at
tp=4 each chip's kernels see 2 of the 8 kv heads); four data-parallel one-chip replicas of
``llama3.2-1b`` against one, on token-set identity; and the paper's pair
— ``llama3.1-8b`` at tp=4 under the exact ruleset, params created
directly into their shardings, AR and then PARD with a replicated
``llama3.2-1b`` draft. The throughput ruleset is not run: at f32 its
replicated embedding tables beside the draft exceed one chip's HBM.

Weights are random, drawn from ``--seed``. Setup is the launcher's own
(``repro.launch.serve``). The script fails unless every request completes
with its 64 tokens and every served step holds the Pallas kernels
(``tpu_custom_call`` in the compiled step). It refuses to run, and prints
no result, when JAX finds no TPU. Timings printed per phase are smoke
timings of one run, not benchmark metrics. The last line of stdout is one
JSON object naming the device.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

MODEL = "llama3.2-1b"
TARGET_TP = "llama3.1-8b"
REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 64
MAX_BATCH, MAX_LEN = 8, 256

# (phase, EngineConfig fields) of the one-chip run; every phase is paged
PHASES = (
    ("ar", dict(mode="ar")),
    ("pard_flat", dict(mode="pard", k=8)),
    ("pard_tree", dict(mode="pard", tree=(2, 2, 2, 1))),
    ("pard_flat_int8", dict(mode="pard", k=8, kv_dtype="int8")),
)

# largest relative L2 distance allowed between the served forward's logits
# at tp=4 and at tp=1: bf16 rounding in a different arithmetic order stays
# near 1e-2, while kernels reading another head's KV (a wrong kv-head split)
# change every attention output and the logits with them, at order 1
LOGIT_REL_TOL = 0.1

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and
    persistent-cache hits, summed from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def peak_bytes(devices):
    """``peak_bytes_in_use`` of each of ``devices`` (None where the
    backend keeps no memory stats)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [s["peak_bytes_in_use"] for s in stats]


def agreement(ref_tokens, tokens):
    """Positionwise agreement of two lists of generated-token arrays, and
    the first divergence as (request, position), or None."""
    agree = total = 0
    first = None
    for i, (a, b) in enumerate(zip(ref_tokens, tokens)):
        n = min(len(a), len(b))
        same = a[:n] == b[:n]
        agree += int(same.sum())
        total += max(len(a), len(b))
        if not same.all() and first is None:
            first = (i, int(same.argmin()))
    return agree / max(total, 1), first


def run_phase(name, config, tc, tparams, dc, dparams, prompts, max_new,
              clock):
    """Serve ``prompts`` twice through one Engine: the first pass compiles
    every step variant it meets (set-up), the second runs warm and must
    reproduce the first pass's tokens. Returns the phase record."""
    import numpy as np

    from repro.launch.serve import serve_requests
    from repro.serving.engine import Engine

    eng = Engine(tparams, tc, dparams, dc, config=config)
    secs0, hits0 = clock.secs, clock.hits
    _, setup_s = serve_requests(eng, prompts, max_new)
    compile_s, hits = clock.secs - secs0, clock.hits - hits0
    _, warm_s = serve_requests(eng, prompts, max_new)

    comps = sorted(eng.completions, key=lambda c: c.rid)
    n = len(prompts)
    cold, warm = comps[:n], comps[n:]
    gen = [np.asarray(c.tokens[len(p):]) for c, p in zip(cold, prompts)]
    repeat = all(np.array_equal(a.tokens, b.tokens)
                 for a, b in zip(cold, warm)) and len(warm) == n
    devices = sorted({d for rep in eng.sched.replicas
                      for d in rep.ex.state.gen.sharding.device_set},
                     key=lambda d: d.id)
    tree = eng.bank is not None
    kernels = all("tpu_custom_call" in rep.ex.step_hlo(tree=tree)
                  for rep in eng.sched.replicas)
    rec = dict(
        phase=name, completed=len(cold), requests=n,
        full_length=all(c.generated == max_new for c in cold),
        generated=int(sum(c.generated for c in cold)),
        in_vocab=all(((g >= 0) & (g < tc.vocab_size)).all() for g in gen),
        repeat_identical=repeat,
        mean_accepted=(None if config.mode == "ar"
                       else round(eng.mean_accepted(), 4)),
        compile_s=round(compile_s, 3), cache_hits=hits,
        setup_s=round(setup_s, 3), warm_s=round(warm_s, 3),
        peak_bytes_in_use=peak_bytes(devices),
        devices=len(devices), tpu_custom_call=kernels, tokens=gen)
    del eng
    gc.collect()
    return rec


def report(rec, ref=None, ref_name="ar"):
    """Print one phase line; with ``ref`` also its greedy agreement."""
    line = " ".join(f"{k}={v}" for k, v in rec.items() if k != "tokens")
    line += " (setup_s/warm_s: smoke timings of one run, not metrics)"
    if ref is not None:
        rate, first = agreement(ref["tokens"], rec["tokens"])
        rec["first_divergence"] = first
        line += f" match_vs_{ref_name}={rate:.4f} first_divergence={first}"
    log(line)


def divergence_gap(cfg, params, prompts, ref, rec):
    """At a phase's first divergence from its reference, rerun the target
    on that request's prefix (one full forward) and print the ranks of the
    two competing tokens and the top-1/top-2 logit gap: a near-tie between
    the top two says the divergence is arithmetic order, not a fault."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward

    i, j = rec["first_divergence"]
    want, got = int(ref["tokens"][i][j]), int(rec["tokens"][i][j])
    toks = np.concatenate([prompts[i], ref["tokens"][i][:j]])[None]
    logits = jax.jit(lambda p, t: forward(p, cfg, t)[0][0, -1])(
        params, jnp.asarray(toks, jnp.int32))
    row = np.asarray(logits, np.float32)[:cfg.vocab_size]
    order = np.argsort(-row)
    rank = {t: int(np.flatnonzero(order == t)[0]) for t in (want, got)}
    log(f"divergence phase={rec['phase']} request={i} position={j} "
        f"ref_token_rank={rank[want]} phase_token_rank={rank[got]} "
        f"top1_top2_gap={row[order[0]] - row[order[1]]:.4g} "
        f"logit_std={row.std():.4g}")


def served_logits(cfg, params, prompts, mesh=None):
    """Logits of the serving steps' forward path on ``prompts``: a prompt
    chunk into a paged bf16 pool (the contiguous decode kernel over the
    gathered block view), then a 9-token verify window (the paged kernel).
    With a mesh, params and pool take the exact ruleset's tensor-parallel
    layout and the forward traces under its activation mesh, as the
    Executor's steps do, so the kernels run split over the kv heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import forward
    from repro.serving import kv_pool
    from repro.sharding import specs

    tokens = jnp.asarray(np.stack(prompts), jnp.int32)
    b, t = tokens.shape
    w, bs = 9, 64
    mbs = -(-t // bs)
    tables = jnp.arange(1, 1 + b * mbs, dtype=jnp.int32).reshape(b, mbs)
    caches = kv_pool.init_paged_caches(cfg, b, 1 + b * mbs, bs, mesh=mesh)
    if mesh is not None:
        params = jax.device_put(params, specs.to_named(specs.param_specs(
            params, mesh, serving=True, ruleset="exact"), mesh))

    def run(params, caches):
        kw = dict(block_tables=tables, kv_block_size=bs)
        _, caches, _ = forward(params, cfg, tokens[:, :-w], caches=caches,
                               cache_pos=jnp.zeros((b,), jnp.int32), **kw)
        return forward(params, cfg, tokens[:, -w:], caches=caches,
                       cache_pos=jnp.full((b,), t - w, jnp.int32), **kw)[0]

    with ops.activation_mesh(mesh, "exact"):
        logits = jax.jit(run)(params, caches)
    return np.asarray(logits, np.float32)[..., :cfg.vocab_size]


def compare_logits(name, ref, got):
    """Print how far ``got`` is from ``ref`` and raise past
    ``LOGIT_REL_TOL``."""
    import numpy as np

    diff = got - ref
    rel = float(np.linalg.norm(diff) / np.linalg.norm(ref))
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"logits {name} rel_l2_err={rel:.4g} "
        f"max_abs_err={float(np.abs(diff).max()):.4g} "
        f"logit_std={float(ref.std()):.4g} argmax_agree={agree:.4f}")
    if not rel < LOGIT_REL_TOL:
        raise SmokeFailure(f"logits {name}: relative error {rel:.4g} is "
                           f"not below {LOGIT_REL_TOL}")


def check(rec):
    """Raise unless every request completed with its full length and
    the served steps held the Pallas kernels."""
    problems = []
    if rec["completed"] != rec["requests"] or not rec["full_length"]:
        problems.append(f"{rec['completed']}/{rec['requests']} requests "
                        f"completed with full length={rec['full_length']}")
    if not rec["in_vocab"]:
        problems.append("generated tokens outside the vocabulary")
    if not rec["repeat_identical"]:
        problems.append("the warm pass did not reproduce the first pass")
    if not rec["tpu_custom_call"]:
        problems.append("no tpu_custom_call in the served step")
    if problems:
        raise SmokeFailure(f"phase {rec['phase']}: " + "; ".join(problems))


def check_kernels(seed):
    """The paged decode (bf16, int8) and tree kernels on the chip against
    the ``kernels/ref.py`` oracles, at ``llama3.2-1b`` head shapes
    (Hq=32, Hkv=8, D=64, 64-slot pages) and the verify windows the
    phases use (flat k=8: 9 queries; tree 2,2,2,1: 23)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.models.attention import quantize_kv

    b, hq, hkv, d, bs, mbs = 8, 32, 8, 64, 64, 4
    nb = 1 + b * mbs
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    k_pages = jax.random.normal(ks[0], (nb, bs, hkv, d), jnp.float32)
    v_pages = jax.random.normal(ks[1], (nb, bs, hkv, d), jnp.float32)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(b, mbs),
                         jnp.int32)
    worst = 0.0
    for tq in (9, 23):
        q = jax.random.normal(ks[2], (b, tq, hq, d), jnp.float32)
        win_start = jnp.asarray(rng.integers(bs, bs * mbs - tq, size=b),
                                jnp.int32)
        kv_len = win_start + tq
        q_pos = win_start[:, None] + jnp.arange(tq)[None, :]
        # random ancestor masks; each slot sees itself
        bits = rng.integers(0, 2, size=(b, tq, tq)) | np.eye(tq, dtype=int)
        anc = jnp.asarray((bits << np.arange(tq)).sum(-1), jnp.uint32)
        kq, sk = quantize_kv(k_pages, jnp.int8)
        vq, sv = quantize_kv(v_pages, jnp.int8)
        cases = (
            ("decode_paged_bf16",
             lambda: ops.decode_attention_paged(
                 q.astype(jnp.bfloat16), k_pages.astype(jnp.bfloat16),
                 v_pages.astype(jnp.bfloat16), tables, kv_len, q_pos),
             lambda: ref.decode_attention_paged_ref(
                 q, k_pages, v_pages, tables, kv_len, q_pos)),
            ("decode_paged_int8",
             lambda: ops.decode_attention_paged(
                 q.astype(jnp.bfloat16), kq, vq, tables, kv_len, q_pos,
                 k_scale=sk, v_scale=sv),
             lambda: ref.decode_attention_paged_ref(
                 q, kq, vq, tables, kv_len, q_pos, k_scale=sk,
                 v_scale=sv)),
            ("tree_paged_bf16",
             lambda: ops.tree_attention_paged(
                 q.astype(jnp.bfloat16), k_pages.astype(jnp.bfloat16),
                 v_pages.astype(jnp.bfloat16), tables, kv_len, q_pos,
                 win_start, anc),
             lambda: ref.tree_attention_paged_ref(
                 q, k_pages, v_pages, tables, kv_len, q_pos, win_start,
                 anc)),
        )
        for name, kernel, oracle in cases:
            got = np.asarray(jax.jit(kernel)(), np.float32)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(jax.jit(oracle)(), np.float32)
            err = float(np.abs(got - want).max())
            worst = max(worst, err)
            ok = bool(np.isfinite(got).all()) and err < 5e-2
            log(f"kernel={name} tq={tq} max_abs_err={err:.3e} ok={ok}")
            if not ok:
                raise SmokeFailure(f"kernel {name} (tq={tq}) disagrees "
                                   f"with its oracle: max error {err:.3e}")
    return worst


def one_chip(seed, clock):
    import jax

    from repro.configs import get_config
    from repro.launch.serve import load_params, make_prompts
    from repro.serving.config import EngineConfig

    check_kernels(seed)
    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    params = load_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"model={MODEL} params_setup_s={time.perf_counter() - t0:.3f} "
        f"param_bytes={sum(x.nbytes for x in jax.tree.leaves(params))}")
    prompts = make_prompts(cfg.vocab_size, REQUESTS, PROMPT_LEN, seed)
    ref = None
    for name, fields in PHASES:
        config = EngineConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                              seed=seed, **fields)
        # the model drafts for itself: same weights, same vocabulary
        rec = run_phase(name, config, cfg, params, cfg, params, prompts,
                        MAX_NEW, clock)
        report(rec, ref, "ar_bf16" if name.endswith("int8") else "ar")
        check(rec)
        if rec.get("first_divergence"):
            divergence_gap(cfg, params, prompts, ref, rec)
        ref = ref or rec


def placement(params):
    """Bytes of ``params`` on each device that holds any of them."""
    import jax
    per = {}
    for x in jax.tree.leaves(params):
        for s in x.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def four_chips(seed, clock):
    import jax

    from repro.configs import get_config
    from repro.launch.serve import load_params, make_prompts
    from repro.serving.config import EngineConfig

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found "
                           f"{len(jax.devices())}")
    tc, dc = get_config(TARGET_TP), get_config(MODEL)
    prompts = make_prompts(tc.vocab_size, REQUESTS, PROMPT_LEN, seed)

    # the kv-head split at published width: the 1B at tp=4 (the kernels
    # see 2 of its 8 kv heads per chip) against tp=1, served greedy AR and
    # the logits of the served forward path. On the TPU the tokens are not
    # bitwise identical across meshes (a bf16 argmax flips at near-ties
    # when the arithmetic order changes), so the logits decide.
    params = load_params(dc, jax.random.PRNGKey(seed))
    recs, logits = {}, {}
    for tp in (1, 4):
        config = EngineConfig(mode="ar", max_batch=MAX_BATCH,
                              max_len=MAX_LEN, seed=seed, tp=tp,
                              tp_ruleset="exact")
        recs[tp] = run_phase(f"tp{tp}_ar_1b", config, dc, params, None,
                             None, prompts, MAX_NEW, clock)
        report(recs[tp], recs[1] if tp > 1 else None, "tp1_ar_1b")
        check(recs[tp])
        logits[tp] = served_logits(dc, params, prompts, config.mesh)
    if recs[4]["first_divergence"] is not None:
        divergence_gap(dc, params, prompts, recs[1], recs[4])
    compare_logits("tp4_vs_tp1_1b", logits[1], logits[4])
    del logits

    # data parallel: four one-chip replicas against one, same requests
    recs = {}
    for dp in (1, 4):
        config = EngineConfig(mode="pard", k=8, max_batch=MAX_BATCH,
                              max_len=MAX_LEN, seed=seed, dp=dp)
        recs[dp] = run_phase(f"dp{dp}", config, dc, params, dc, params,
                             prompts, MAX_NEW, clock)
        report(recs[dp], recs[1] if dp > 1 else None, "dp1")
        check(recs[dp])
    same = (sorted(map(tuple, recs[1]["tokens"]))
            == sorted(map(tuple, recs[4]["tokens"])))
    log(f"dp4_vs_dp1 token_set_identical={same}")
    if not same:
        raise SmokeFailure("dp=4 replicas changed the generated token set")
    del params
    gc.collect()

    # tensor parallel: the 8B target sharded 4 ways, AR first, then PARD
    # with the 1B draft on every chip (created only then: the AR step's
    # temporaries and the draft would not both fit beside the target)
    ar_config, config = (
        EngineConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, seed=seed, tp=4,
                     tp_ruleset="exact", **fields)
        for fields in (dict(mode="ar"), dict(mode="pard", k=8)))
    t0 = time.perf_counter()
    tparams = load_params(tc, jax.random.PRNGKey(seed), mesh=config.mesh,
                          tp_ruleset="exact")
    jax.block_until_ready(tparams)
    total = sum(x.nbytes for x in jax.tree.leaves(tparams))
    per = placement(tparams)
    log(f"tp=4 target={TARGET_TP} params_setup_s="
        f"{time.perf_counter() - t0:.3f} target_bytes={total} "
        f"target_bytes_per_device={per}")
    if len(per) != 4 or max(per.values()) >= total:
        raise SmokeFailure(f"the tp=4 target is not split over 4 devices: "
                           f"{per} of {total} bytes")
    ar = run_phase("tp4_ar", ar_config, tc, tparams, None, None, prompts,
                   MAX_NEW, clock)
    report(ar)
    check(ar)
    dparams = load_params(dc, jax.random.PRNGKey(seed + 1),
                          mesh=config.mesh, replicate=True)
    rec = run_phase("tp4_pard", config, tc, tparams, dc, dparams, prompts,
                    MAX_NEW, clock)
    report(rec, ar, "tp4_ar")
    check(rec)
    del dparams
    gc.collect()
    if rec["first_divergence"] is not None:
        divergence_gap(tc, tparams, prompts, ar, rec)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving phases (default); 4: the "
                         "tp=4 and dp=4 paths only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
              f"this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{platform!r} ({devices[0].device_kind}); no phase was run",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile_cache={enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    log(f"total_compile_s={clock.secs:.3f} cache_hits={clock.hits} "
        f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
