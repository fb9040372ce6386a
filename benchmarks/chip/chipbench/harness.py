"""One run of one cell: set-up, the timed window, the check, the result.

Set-up (``setup_s``, from process start to the window): JAX, the weights
(one jitted call from the seed, on the device), the engine, a warm-up that
compiles every program the window runs, the traffic, and what the traffic
needs before its window (prefilled contexts, or a lead-in of the loop).
The window then runs for ``--seconds``. After it the served tokens of a
sample of requests, drawn from the seed with the longest among them, are
compared with the plain reference, once the engine's memory is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import devtrace, flops, registry
from . import traffic as traffic_mod
from .feeder import Feeder, Record

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_SECONDS = 4.0            # traced tail of the window (--trace 1)
REF_LENGTH_STEP = 1024         # reference sequences pad to a multiple


class Refused(Exception):
    """The run cannot be measured here (no TPU, too few chips)."""


def enable_cache(checkout) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, for every program."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Backend compile seconds, compiles and persistent-cache hits, summed
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


@dataclasses.dataclass
class Run:
    """What the metric readers (``metrics/<name>.py``) see."""
    cell: registry.Cell
    setup_s: float
    window_s: float
    counted: List[Record]
    output_tokens: int
    output_flops: int
    stats: Dict[str, int]
    chips: int
    device_kind: str
    trace: Optional[dict] = None

    def peak(self, key: str) -> float:
        """One chip's peak ``key`` from peaks.json (unknown kind: error)."""
        return float(registry.peaks(self.cell.bench_dir,
                                    self.device_kind)[key])


def _memory_peak(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def _warm_up(eng, vocab_size: int) -> int:
    """Fill every slot with a short greedy request and serve them all: the
    fused step (prefill chunks and decode rows), admission's row updates
    on every slot, and the harvest compile here, not in the window.
    Returns how many of them did not finish."""
    from repro.serving.engine import SamplingParams
    chunk = eng.sched.chunk
    prompt = (np.arange(2 * chunk + 3) * 7919 % vocab_size).astype(np.int32)
    for _ in range(eng.max_batch):
        eng.submit(prompt, params=SamplingParams(max_new=eng.k + 3,
                                                 temperature=0.0))
    # bounded: a step that makes no progress must not hang the set-up
    done = len(eng.completions)
    eng.run(max_steps=eng.stats["steps"] + 8 * (len(prompt) + eng.k + 3))
    return eng.max_batch - (len(eng.completions) - done)


def _drive(drv: Feeder, loop: str, until: float, *, depth: int = 0,
           origin: float = 0.0, on_tick=None) -> None:
    """Run the loop until the clock reaches ``until`` (the step in flight
    then completes), until ``on_tick(now)`` returns true, or, for a
    preloaded mix, until no work is left."""
    while True:
        now = time.perf_counter()
        if now >= until:
            return
        if on_tick is not None and on_tick(now):
            return
        if loop == "closed":
            drv.top_up(depth)
        elif loop == "open":
            drv.submit_due(origin, now)
        if drv.has_work():
            drv.step()
        elif loop == "open":
            nxt = drv.next_due(origin)
            wake = until if nxt is None else min(nxt, until)
            with devtrace_span("wait_arrival"):
                time.sleep(max(0.0, wake - time.perf_counter()))
        else:
            return


def devtrace_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(devtrace.SPAN_PREFIX + name)


def _prefill_all(drv: Feeder) -> None:
    """Preloaded mix: submit every request and step until no row is still
    consuming its prompt."""
    for req in drv.requests:
        drv.submit(req)
    sched = drv.eng.sched
    # bounded: a step that makes no progress must not hang the set-up
    budget = 4 * sum(len(r.prompt) for r in drv.requests) // sched.chunk + 100
    while (sched.queue or sched.prefilling_count()) and budget > 0:
        drv.step()
        budget -= 1


def _sample(cands: List[tuple], n: int, seed: int) -> List[tuple]:
    """``n`` of (rid, prompt_len, tokens): the one with the most served
    tokens, the rest drawn from the seed."""
    cands = sorted(cands, key=lambda c: (-(len(c[2]) - c[1]), c[0]))
    if len(cands) <= n:
        return cands
    rest = traffic_mod.rng(seed, 7).choice(len(cands) - 1, n - 1,
                                           replace=False)
    return [cands[0]] + [cands[1 + i] for i in sorted(rest)]


def compare(ref, cfg: dict, params, sample: List[tuple],
            control: bool = False) -> dict:
    """How far each served token's logit lies below the reference's best
    at its position, over every served token of ``sample``: the widest
    gap, the mean gap, and the share of tokens that are not the
    reference's first choice. ``control``: the same for the tokens that
    the reference's lower-precision control puts first at those positions
    (``ref.control_gaps``)."""
    import jax
    import jax.numpy as jnp
    gaps_of = ref.control_gaps if control else ref.next_token_gaps
    fn = jax.jit(lambda p, t: gaps_of(p, cfg, t))
    vocab = cfg["vocab_size"]
    gaps, outside = [], 0
    for _, p_len, tokens in sample:
        served = tokens[p_len:]
        outside += int(((served < 0) | (served >= vocab)).sum())
        length = -(-len(tokens) // REF_LENGTH_STEP) * REF_LENGTH_STEP
        padded = np.zeros(length, np.int32)
        padded[:len(tokens)] = tokens
        got = np.asarray(fn(params, jnp.asarray(padded)), np.float64)
        gaps.append(got[p_len - 1:len(tokens) - 1])
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    n = len(gaps)
    return dict(widest=float(gaps.max()) if n else 0.0,
                mean=float(gaps.mean()) if n else 0.0,
                off_top=float((gaps > 0).mean()) if n else 0.0,
                compared=n, outside=outside)


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             *, t0: float, require_tpu: bool = True,
             engine_overrides: Optional[dict] = None,
             reference_control: bool = False,
             draft_of: Optional[Callable] = None) -> dict:
    """Set up, measure, check; returns the result line's object.
    Never in the benchmark's own runs: ``reference_control`` adds the
    control's numbers on the same sample under the key ``control``, and
    ``draft_of`` makes the draft's weights from the target's."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise Refused(f"needs a TPU, but JAX found platform {platform!r} "
                      f"({devices[0].device_kind})")
    if len(devices) < cell.chips:
        raise Refused(f"cell {cell.name} needs {cell.chips} chips, JAX "
                      f"found {len(devices)}")
    used = devices[:cell.chips]
    clock = CompileClock()
    t_jax = time.perf_counter()

    from repro.models.config import ModelConfig
    from repro.serving.engine import Engine, EngineConfig

    cfg = cell.config
    ref = registry.reference(cell.bench_dir, cfg["model_type"])
    model = ModelConfig(**ref.model_config(cfg))
    jseed = traffic_mod.jax_seed(seed)
    params = jax.jit(lambda k: ref.init_params(cfg, k))(
        jax.random.PRNGKey(jseed))
    jax.block_until_ready(params)
    t_params = time.perf_counter()

    fields = dict(cell.engine, **(engine_overrides or {}))
    # the model drafts for itself: the same weights serve target and draft
    draft = params if draft_of is None else draft_of(params)
    eng = Engine(params, model, draft, model,
                 config=EngineConfig(seed=jseed, **fields))
    warm_unfinished = _warm_up(eng, model.vocab_size)
    t_warm = time.perf_counter()

    traffic = cell.traffic
    loop = traffic["loop"]
    requests = traffic_mod.make_requests(traffic, model.vocab_size, seed,
                                         seconds)
    drv = Feeder(eng, requests)
    t_traffic = time.perf_counter()
    depth = traffic.get("queue_depth", "max_batch")
    depth = eng.max_batch if depth == "max_batch" else int(depth)
    origin = time.perf_counter()
    if loop == "preload":
        _prefill_all(drv)
    else:
        # rows enter the window at staggered points of their requests
        _drive(drv, loop, origin + float(traffic.get("lead_s", 0.0)),
               depth=depth, origin=origin)
    compiles0, secs0 = clock.compiles, clock.secs
    w0 = time.perf_counter()
    setup_s = w0 - t0
    log(f"setup jax_s={t_jax - t0:.3f} params_s={t_params - t_jax:.3f} "
        f"engine_warmup_s={t_warm - t_params:.3f} "
        f"compile_s={clock.secs:.3f} compiles={clock.compiles} "
        f"cache_hits={clock.hits} traffic_s={t_traffic - t_warm:.3f} "
        f"before_window_s={w0 - t_traffic:.3f} setup_s={setup_s:.3f}")

    # ------------------------------------------------------------ window
    start = drv.committed()
    stats0 = drv.stats()
    t_end = w0 + float(seconds)
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    stack = contextlib.ExitStack()
    tracing = []

    def on_tick(now):
        if tmp and not tracing and now >= t_end - TRACE_SECONDS:
            stack.enter_context(devtrace.capture(tmp))
            stack.enter_context(devtrace_span("traced"))
            tracing.append(now)
        return False

    with stack:                  # the trace, if any, stops after the window
        _drive(drv, loop, t_end, depth=depth, origin=origin,
               on_tick=on_tick)
        w1 = time.perf_counter()
    end = drv.committed()
    stats1 = drv.stats()
    window_compiles = clock.compiles - compiles0
    log(f"window seconds={w1 - w0:.3f} steps={stats1['steps'] - stats0['steps']} "
        f"compiles={window_compiles} compile_s={clock.secs - secs0:.3f}")

    live = drv.live_outputs() if loop != "open" else {}
    unfinished = 0
    if loop == "open":
        drain = float(traffic.get("drain_s", 60.0))
        pending = [r for r in drv.records if r.counted]
        limit = w1 + drain

        _drive(drv, loop, limit, origin=origin,
               on_tick=lambda _: all(r.done is not None for r in pending))
        unfinished = sum(r.done is None for r in pending)
        log(f"drain seconds={time.perf_counter() - w1:.3f} "
            f"unfinished_due={unfinished}")
    memory_peak = _memory_peak(used)

    # ------------------------------------------------------------ account
    out_tokens, out_flops = 0, 0
    for rid, n_end in end.items():
        n_start = start.get(rid, drv.by_rid[rid].prompt_len)
        out_tokens += n_end - n_start
        out_flops += flops.output_flops(cfg, n_start, n_end)
    counted = [r for r in drv.records if r.counted]
    served = [r for r in drv.records
              if r.submitted is not None and r.submitted < w1
              and (r.done is None or r.done >= w0)]
    attempted = len(counted) if loop == "open" else len(served)
    failed = unfinished

    cands = [(r.rid, r.prompt_len, r.tokens) for r in drv.records
             if r.tokens is not None
             and (r.counted if loop == "open" else r.done >= w0)]
    cands += [(rid, drv.by_rid[rid].prompt_len, toks)
              for rid, toks in live.items()]
    sample = _sample(cands, int(cell.check["sample"]), seed)
    del drv, eng, draft
    gc.collect()

    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got = compare(ref, cfg, params, sample)
    log(f"reference requests={len(sample)} tokens={got['compared']} "
        f"not_first_choice={got['off_top']:.5f} "
        f"seconds={time.perf_counter() - t_ref:.3f}")
    ctl = None
    if reference_control:
        with jax.default_matmul_precision("highest"):
            ctl = compare(ref, cfg, params, sample, control=True)
        log(f"control max_logit_gap={ctl['widest']} "
            f"mean_logit_gap={ctl['mean']} not_first_choice={ctl['off_top']}")

    reduced = None
    if tmp:
        tr = devtrace.load(tmp)
        traced = [s for s in tr.spans if s.name == "bench.traced"]
        if traced:
            reduced = devtrace.reduce(tr, traced[0].start_ns,
                                      traced[0].end_ns)
        shutil.rmtree(tmp, ignore_errors=True)

    run = Run(cell=cell, setup_s=setup_s, window_s=w1 - w0, counted=counted,
              output_tokens=out_tokens, output_flops=out_flops,
              stats={k: stats1[k] - stats0[k] for k in stats1},
              chips=len(used), device_kind=used[0].device_kind,
              trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = registry.metric_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limit = cell.check
    live_steps = run.stats["live_steps"]
    accepted = 1.0 + run.stats["accepted"] / live_steps if live_steps else 0.0
    checks = [
        ("max_logit_gap", got["widest"], "<=", float(limit["max_logit_gap"])),
        ("mean_logit_gap", got["mean"], "<=", float(limit["mean_logit_gap"])),
        # greedy verification hides a faulty draft; its acceptance does not
        ("mean_accepted", accepted, ">=", float(limit["min_mean_accepted"])),
        ("compared_tokens", got["compared"], ">=", int(limit["min_tokens"])),
        ("out_of_vocab_tokens", got["outside"], "<=", 0),
        ("unfinished_warmup_requests", warm_unfinished, "<=", 0),
    ]
    if loop == "open":
        checks.append(("unfinished_due_requests", unfinished, "<=", 0))
    correct = all(v <= lim if op == "<=" else v >= lim
                  for _, v, op, lim in checks)
    device = {"platform": platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"] if reduced else None
        device["window_s"] = reduced["window_s"] if reduced else None
        if reduced:
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    if ctl is not None:
        result["control"] = {"max_logit_gap": ctl["widest"],
                             "mean_logit_gap": ctl["mean"],
                             "compared_tokens": ctl["compared"]}
    result["checks"] = {name: {"value": v, "limit": lim, "holds": op}
                        for name, v, op, lim in checks}
    log(f"run output_tokens={out_tokens} window_s={w1 - w0:.3f} "
        f"stats={json.dumps(run.stats)}")
    for name, v, op, lim in checks:
        log(f"check {name}={v} limit {op} {lim}")
    return result
