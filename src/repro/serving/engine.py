"""Batched serving engine — the thin facade over the layered serving stack
(DESIGN.md §8).

The engine is three layers with one owner each:

  * ``serving.scheduler.Scheduler`` (host): request queue, FIFO-fair
    skip-ahead admission, chunked-prefill budgeting, prefix-cache matching,
    adaptive tree-template control, per-request latency accounting (queue
    wait, TTFT, per-token p50/p95);
  * ``serving.executor.Executor`` (device): the ONE ``DecodeState``
    (core.spec_decode), the KV pools, and the fused jitted step functions;
  * ``Engine`` (this module): construction + the run loop, preserving the
    original public API (``submit`` / ``run`` / ``stats`` / KV accounting)
    so existing callers and tests keep working.

Design (all fixed shapes, jit-once):
  * PREFILL IS A STEP WORKLOAD, not an admission one: admission only claims
    a slot + KV blocks and writes the prompt into the generation buffer;
    the fused step then advances decoding rows AND consumes a bounded
    prompt chunk for every prefilling row in the SAME forward (Sarathi-
    style chunked prefill) — no per-request ``[1, P_bucket]`` prefill
    forwards, no jit cache over prompt buckets, and admission never stalls
    live decode rows;
  * KV lives in block-paged pools (serving/kv_pool.py — fixed-size
    blocks, per-slot block tables, refcounted free-list allocation, O(1)
    release);
  * ``prefix_cache=True`` reuses prompt KV across requests:
    full prompt blocks register in a content-keyed index, admission maps
    the longest computed block-aligned prefix copy-free into the new row's
    table (refcount + 1, target and draft keyed together) and only
    prefills the tail; refcount-0 cached blocks are evicted LRU;
  * decode: ONE jitted speculative step advances all active slots together;
    finished slots free immediately and new requests admit on the next tick
    (continuous batching);
  * ``run(pipelined=True)`` / ``run_pipelined()`` overlap host scheduling
    with device execution: step t+1 is dispatched (donated state buffers,
    staged mutations) while step t's results are still in flight, and every
    step's outputs arrive in one batched transfer (DESIGN.md §9) —
    token-identical to the synchronous loop;
  * modes: "ar" (AR+ baseline), "vsd", "pard" — same engine, same pool;
    ``tree=`` upgrades "pard" to tree-structured drafting (DESIGN.md §6),
    per-request via a TemplateBank, ``adaptive_tree=True`` adds the EWMA
    controller (DESIGN.md §7);
  * sampling is per REQUEST (``submit(..., temperature=)``), each request
    drawing from its own (seed, rid) PRNG key — deterministic per request
    across batch compositions.

SSM/hybrid targets work unchanged: chunked prefill gathers the recurrent
state after each chunk's last real token (DESIGN.md §3), admission zeroes
the recycled slot's state, and SSM states stay batch-indexed beside the
attention pools.
"""
from __future__ import annotations

import warnings
from collections import deque
from typing import Optional

from ..core.spec_decode import SpecDecoder
from ..models.config import ModelConfig
from . import kv_pool, spans
from .config import EngineConfig, SamplingParams  # noqa: F401  (re-export)
from .executor import Executor
from .scheduler import (Completion, Request, Scheduler,  # noqa: F401
                        TreeController)


class Engine:
    """Primary construction path: ``Engine(tp, tc, dp, dc, config=cfg)``
    with a typed, validated ``EngineConfig`` (serving/config.py). The
    historical loose-kwargs form still works — it builds the same config
    through a DeprecationWarning shim — so existing callers keep running
    while new code gets one construction surface."""

    def __init__(self, target_params, target_cfg: ModelConfig,
                 draft_params=None, draft_cfg: Optional[ModelConfig] = None, *,
                 config: Optional[EngineConfig] = None, **legacy):
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or the legacy "
                    f"keyword arguments, not both (got {sorted(legacy)})")
            warnings.warn(
                "Engine(**kwargs) is deprecated; build an EngineConfig and "
                "pass Engine(params, cfg, ..., config=engine_config)",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        elif config is None:
            config = EngineConfig()
        self.config = config
        self.adaptive = config.adaptive_tree
        self.mode = mode = config.mode
        self.k = config.k if mode != "ar" else 1
        if mode == "ar":
            # the AR baseline never reads draft caches: drop the draft model
            # so admission skips its KV accounting entirely
            draft_params = draft_cfg = None
        self.max_batch = max_batch = config.max_batch
        self.max_len = max_len = config.max_len
        self.eos_id = config.eos_id
        self.temperature = config.temperature  # submit(temperature=None)
        self.mesh = config.mesh                # None = single-device serving
        # data-parallel replicas (DESIGN.md §12): dp > 1 splits the mesh
        # into one (1, tp) row per replica and builds one (SpecDecoder,
        # BlockAllocator, Executor) triple on each — independent device
        # programs with their own DecodeState and KV pool behind the one
        # host-side scheduler. dp=1 is the historical single-triple path.
        self.dp = dp = config.dp
        if dp > 1:
            from ..launch.mesh import replica_submeshes
            meshes = replica_submeshes(config.mesh)
        else:
            meshes = [config.mesh]
        decs = [SpecDecoder(
            target_params, target_cfg, draft_params, draft_cfg, k=self.k,
            max_len=max_len, temperature=config.temperature,
            kv_block_size=config.kv_block_size,
            tree=config.tree if mode == "pard" else None,
            prefill_chunk=config.prefill_chunk, kv_dtype=config.kv_dtype,
            mesh=m, tp_ruleset=config.tp_ruleset) for m in meshes]
        self.dec = decs[0]
        self.k = self.dec.k          # a tree template overrides k (== depth)
        self.bank = self.dec.tree    # TemplateBank (or None: no tree)
        self.tc, self.dc = target_cfg, draft_cfg

        # kv_num_blocks is PER REPLICA: each replica owns a full pool
        nb = config.kv_num_blocks or kv_pool.default_num_blocks(
            max_batch, max_len, config.kv_block_size)
        # the shared cross-replica prefix index admission routes over;
        # pointless (and absent) with a single replica
        self.prefix_index = kv_pool.PrefixIndex() if dp > 1 else None
        allocs = [kv_pool.BlockAllocator(
            nb, config.kv_block_size, max_batch, max_len, replica=r,
            prefix_index=self.prefix_index) for r in range(dp)]
        self.alloc = allocs[0]
        exs = [Executor(decs[r], target_cfg, draft_cfg, mode, max_batch,
                        max_len, nb, config.seed, mesh=meshes[r], replica=r,
                        tp_ruleset=config.tp_ruleset) for r in range(dp)]
        self.ex = exs[0]
        ctrl = (TreeController(self.bank, max_batch * dp, config.tree_ewma)
                if config.adaptive_tree else None)
        self.sched = Scheduler(
            decs if dp > 1 else decs[0], exs if dp > 1 else exs[0],
            allocs if dp > 1 else allocs[0], mode=mode, max_batch=max_batch,
            max_len=max_len, temperature=config.temperature,
            eos_id=config.eos_id, bank=self.bank, ctrl=ctrl,
            prefix_cache=config.prefix_cache,
            admit_window=config.admit_window,
            prefill_budget=config.prefill_budget,
            tree_reselect_every=config.tree_reselect_every,
            prefix_index=self.prefix_index)
        self.ctrl = ctrl
        self.peak_kv_bytes_in_use = 0

    # ------------------------------------------------------------- public
    def submit(self, prompt, max_new: Optional[int] = None,
               temperature: Optional[float] = None,
               tree_idx: Optional[int] = None,
               params: Optional[SamplingParams] = None) -> int:
        """Queue a request. Preferred: ``submit(prompt, params=
        SamplingParams(max_new=.., temperature=.., seed=.., tree_idx=..))``.
        The loose keywords still work (``temperature`` overrides the engine
        default for this request only, 0 = greedy; ``tree_idx`` pins one
        bank template) and fold into the same SamplingParams. Validation
        happens here, with the request's OWN window slack — see
        Scheduler.submit."""
        return self.sched.submit(prompt, max_new, temperature, tree_idx,
                                 params=params)

    def run(self, max_steps: int = 100000,
            pipelined: Optional[bool] = None):
        """Drive the serve loop to completion. ``pipelined=False`` runs
        the depth-1 (synchronous) pipeline: each step is dispatched and
        its results processed back-to-back — the exact historical
        semantics. ``pipelined=True`` runs depth 2: step t+1 is dispatched
        (with the mutations staged from step t-1's results) BEFORE step
        t's results are harvested, so host-side scheduling overlaps device
        execution (DESIGN.md §9). Both depths share this one loop; the
        only difference is how many handles may be in flight.
        ``pipelined=None`` defaults to ``config.pipelined``."""
        if pipelined is None:
            pipelined = self.config.pipelined
        sched = self.sched
        depth = 2 if pipelined else 1
        # one independent dispatch/harvest pipeline PER replica: each
        # replica's handles retire in its own dispatch order, and all
        # replicas' steps are dispatched back-to-back before any harvest
        # blocks (on real multi-device hardware the replicas' device work
        # overlaps; dp=1 reduces to the single historical deque)
        inflight = {rep.rep: deque() for rep in sched.replicas}

        def pending() -> int:
            return sum(len(q) for q in inflight.values())

        while sched.has_work() or pending():
            dispatched = False
            if sched.has_work() and sched.stats["steps"] < max_steps:
                admitted = sched.admit()
                if sched.queue and not admitted and not pending() \
                        and not any(rep.has_live()
                                    for rep in sched.replicas):
                    # every slot (hence every block) is free, nothing is in
                    # flight that could free more, and NOTHING in the
                    # admission window could admit: the head can never fit
                    # — fail loudly instead of spinning forever
                    req = sched.queue[0]
                    raise RuntimeError(
                        f"request {req.rid} (prompt={len(req.prompt)}, "
                        f"max_new={req.max_new}) needs more KV blocks than "
                        f"the pool holds; raise kv_num_blocks or max_len")
                with spans.phase(sched.stats, "sync_tables"):
                    for rep in sched.replicas:
                        rep.ex.sync_tables(rep.alloc)
                    self.peak_kv_bytes_in_use = max(
                        self.peak_kv_bytes_in_use, self.kv_bytes_in_use())
                for rep in sched.replicas:
                    if rep.has_live():
                        inflight[rep.rep].append(sched.dispatch(rep.rep))
                        dispatched = True
            for q in inflight.values():
                if q and (len(q) >= depth or not dispatched):
                    sched.process(q.popleft())
            if not dispatched and not pending():
                break                  # step budget exhausted, fully drained
        if not sched.has_work():
            # the wait for new requests is not host overhead of a step
            sched._harvest_done_ns = None
        return sched.completions

    def run_pipelined(self, max_steps: int = 100000):
        """``run`` with the two-deep dispatch/harvest pipeline."""
        return self.run(max_steps, pipelined=True)

    def mean_accepted(self) -> float:
        return self.sched.mean_accepted()

    def prefix_hit_rate(self) -> float:
        return self.sched.prefix_hit_rate()

    def latency_summary(self):
        return self.sched.latency_summary()

    def kv_capacity_bytes(self) -> int:
        """HBM resident for the attention KV cache (target + draft),
        summed over all replicas."""
        return sum(rep.ex.kv_capacity for rep in self.sched.replicas)

    def kv_bytes_in_use(self) -> int:
        """KV bytes backing live requests, summed over all replicas: each
        UNIQUE mapped block counts once (prefix-shared blocks are the point
        of sharing)."""
        return sum(rep.alloc.blocks_in_use * rep.ex.kv_per_block
                   for rep in self.sched.replicas)

    # --------------------------------------------------- facade accessors
    @property
    def state(self):
        return self.ex.state

    @property
    def stats(self):
        return self.sched.stats

    @property
    def queue(self):
        return self.sched.queue

    @property
    def slots(self):
        return self.sched.slots

    @property
    def completions(self):
        return self.sched.completions
