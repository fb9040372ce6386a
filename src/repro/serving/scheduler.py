"""Host-side half of the serving stack (DESIGN.md §8).

The ``Scheduler`` owns every decision that does NOT touch the device:

  * request queue + FIFO-fair skip-ahead admission: a bounded prefix of the
    queue (``admit_window``) is scanned per free slot, so one pool-oversized
    request cannot starve smaller ones behind it while blocks are free —
    the head admits the moment its resources exist, and nothing beyond the
    window may overtake it;
  * chunked-prefill budgeting: admission caps CONCURRENT prefilling rows at
    ``prefill_budget // chunk_width`` lanes so a burst of long prompts
    cannot crowd decode compute out of the fused steps (None = no throttle;
    full-prefix cache hits consume no lane);
  * prefix-cache admission: the longest computed block-aligned prefix of
    the new prompt is mapped copy-free from ``kv_pool.BlockAllocator``'s
    hash index, and only the uncovered tail is prefilled (the prefill
    cursor starts past the hit);
  * per-request latency accounting: queue wait, TTFT, per-token
    inter-commit latency percentiles, and per-step host overhead (the
    wall time from harvest-complete to the next dispatch), recorded on
    every ``Completion`` and summarised by ``latency_summary``;
  * the loop's host phases as profiler spans and nanosecond counters in
    ``stats`` (serving/spans.py), and the paged-KV page counts of each
    step (``kv_pages_live`` / ``kv_pages_grid``);
  * the adaptive tree-template controller (``TreeController``) and the
    between-windows reshaping cadence.

Stepping follows the executor's dispatch/harvest split (DESIGN.md §9):
``dispatch()`` issues one fused step non-blocking — applying every staged
mutation (retirements from the previous harvest, template re-selection)
on device ahead of the inner step — and immediately advances all
DISPATCH-DETERMINISTIC accounting: step counters, the prefill cursor
mirrors (the chunk schedule depends only on the cursor, never on step
results), computed-block flags. ``process(handle)`` harvests a step's
results in one batched transfer and folds in everything RESULT-DEPENDENT:
acceptance stats and controller updates from the device-reported live
mask, completions (EOS / max_new), and retirement — STAGED, applied at
the next dispatch boundary. The synchronous loop is the depth-1 special
case of the same protocol (dispatch immediately followed by process), so
the pipelined loop's semantics are the synchronous ones shifted by at
most one step.

Data parallelism (DESIGN.md §12): the scheduler can manage SEVERAL
executor replicas — each an independent ``(SpecDecoder, Executor,
BlockAllocator)`` triple with its own ``DecodeState`` and KV pool on its
own mesh row — behind the ONE shared queue. Per-replica host mirrors live
in ``_Replica`` records; admission routes each request by
prefix-affinity-then-least-loaded over the shared content-keyed prefix
index (a replica already holding the prompt's cached blocks gets the
request; misses go to the emptiest replica; a full preferred replica is
skipped, never stalled on). With ``dp=1`` every path below degenerates to
the historical single-engine behaviour.

Device work (cache pools, jitted fused steps, row state) lives in
``serving.executor.Executor``; ``serving.engine.Engine`` is the thin
facade wiring the two together.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..core.spec_decode import SpecDecoder, TemplateBank
from ..kernels.decode_attention import sweep_group
from . import kv_pool, spans
from .config import SamplingParams
from .executor import NO_LIMIT, Executor, StepHandle, StepResult


@dataclasses.dataclass
class Request:
    """One queued request. Per-request decode options travel as ONE
    ``SamplingParams`` value object (serving/config.py); the flat
    accessors below keep every consumer of the old loose fields
    (admission, template selection, completion accounting) unchanged."""
    rid: int
    prompt: np.ndarray          # 1-D int32
    params: SamplingParams

    @property
    def max_new(self) -> int:
        return self.params.max_new

    @property
    def temperature(self) -> Optional[float]:
        return self.params.temperature    # None = the engine default

    @property
    def tree_idx(self) -> Optional[int]:
        return self.params.tree_idx       # pinned bank template (None =
        #                                   controller / template 0)

    @property
    def seed(self) -> Optional[int]:
        return self.params.seed           # None = (engine seed, rid) stream


@dataclasses.dataclass
class Completion:
    """A finished request as handed back by ``Engine.run``: the committed
    tokens plus the per-request latency accounting (all wall-clock
    seconds; ``tok_*`` are inter-commit percentiles in milliseconds)."""

    rid: int
    tokens: np.ndarray          # prompt + generated
    generated: int
    wall_submitted: float
    wall_done: float
    queue_wait: float = 0.0     # submit -> admission
    ttft: float = 0.0           # submit -> first generated token committed
    tok_p50: float = 0.0        # per-token inter-commit latency percentiles
    tok_p95: float = 0.0


def _weighted_percentile(samples: List, q: float) -> float:
    """Percentile over (value, weight) pairs — weights are token counts, so
    a step that committed 3 tokens contributes its per-token latency x3."""
    if not samples:
        return 0.0
    vals = np.repeat([v for v, _ in samples], [c for _, c in samples])
    return float(np.percentile(vals, q))


class TreeController:
    """Acceptance-statistics template selection (DESIGN.md §7).

    Maintains, per slot and per (depth d, sibling rank c), an EWMA of the
    indicator "depth d was evaluated this step and rank c's candidate was
    the accepted one" — updated ONLY at steps where rank c was actually
    OFFERED (c < the in-use template's branching at d), so the estimate is
    the conditional accept probability P(rank c wins | depth d reached,
    rank c offered) regardless of which template happened to be active.
    A template's score is its expected accepted length under independence
    across ranks: E(t) = sum_d prod_{d' <= d} min(1, sum_{c < b_d'} p[d',c]).

    New requests have no history, so admission selects on a GLOBAL EWMA
    that every retiring request folds its learned row into; per-slot rows
    are seeded from the global one at admission and drive the between-
    windows re-selection (``Scheduler._reshape_slots``).
    """

    def __init__(self, bank: TemplateBank, max_batch: int, ewma: float = 0.2):
        self.bank = bank
        self.ewma = ewma
        d, mb = bank.max_depth, bank.max_branching
        self.offer = np.zeros((len(bank), d), np.int32)   # [T, D] branching
        for t, tpl in enumerate(bank.templates):
            self.offer[t] = tpl.branching
        # optimistic prior: rank 0 accepts half the time, each extra rank
        # adds a little — wide templates stay in play until data arrives
        prior = np.zeros((d, mb))
        prior[:, 0] = 0.5
        if mb > 1:
            prior[:, 1:] = 0.15
        self.global_p = prior.copy()
        self.slot_p = np.tile(prior, (max_batch, 1, 1))
        # cached scoring machinery (the adaptive-tree host hot path runs
        # every harvested step over dp*max_batch rows — keep it vectorized):
        # offered-rank mask per template [T, D, mb] and scratch rank ids
        self._offer_mask = (np.arange(mb)[None, None, :]
                            < self.offer[:, :, None])
        self._ranks = np.arange(mb)
        self._depths = np.arange(d)

    def seed_slot(self, slot: int) -> None:
        self.slot_p[slot] = self.global_p

    def retire_slot(self, slot: int) -> None:
        """Fold a finished request's learned statistics into the admission
        prior (an EWMA over requests, like the per-step one over windows)."""
        self.global_p += 0.5 * (self.slot_p[slot] - self.global_p)

    def update(self, live: np.ndarray, tree_idx: np.ndarray, a: np.ndarray,
               rank: np.ndarray) -> None:
        """live [B] (rows decoding BEFORE the step), tree_idx [B], a [B]
        accepted depths, rank [B, D] accepted sibling rank per depth (-1
        where the depth rejected or was never reached).

        One vectorized EWMA write over [live, D, mb]: a cell (slot, dep, c)
        updates iff the depth was evaluated this step (dep <= a — depths
        1..a accepted, depth a+1 evaluated and rejected, deeper ones carry
        no information) AND rank c was offered (c < the in-use template's
        branching at dep). Cell updates are independent, so this computes
        bit-identical values to the scalar triple loop it replaced."""
        idx = np.nonzero(live)[0]
        if idx.size == 0:
            return
        br = self.offer[np.asarray(tree_idx)[idx]]            # [n, D]
        evaluated = self._depths[None, :] <= np.asarray(a)[idx, None]
        offered = self._ranks[None, None, :] < br[:, :, None]  # [n, D, mb]
        upd = evaluated[:, :, None] & offered
        obs = (np.asarray(rank)[idx][:, :, None]
               == self._ranks[None, None, :]).astype(self.slot_p.dtype)
        p = self.slot_p[idx]
        self.slot_p[idx] = np.where(upd, p + self.ewma * (obs - p), p)

    def select(self, slot: Optional[int] = None,
               feasible=None) -> int:
        """Best-scoring template (per-slot stats, or the global prior for
        admission). ``feasible``: optional iterable of permitted template
        indices (allocation / max_len constraints)."""
        p = self.global_p if slot is None else self.slot_p[slot]
        cands = range(len(self.bank)) if feasible is None else list(feasible)
        # all templates scored in one shot against the cached offered-rank
        # masks: s[t, d] = min(1, sum_{c < b_d} p[d, c]), E(t) = sum of the
        # depth-wise survival cumprod
        s = np.minimum(1.0, np.where(self._offer_mask, p[None], 0.0).sum(-1))
        scores = np.cumprod(s, axis=1).sum(axis=1)
        best, best_e = next(iter(cands)), -1.0
        for t in cands:   # keep the earliest-wins 1e-9 tie-break semantics
            if scores[t] > best_e + 1e-9:
                best, best_e = t, float(scores[t])
        return best


class _Replica:
    """Host-side mirrors for ONE engine replica (DESIGN.md §12): its
    decoder/executor/allocator triple plus every per-slot array the
    scheduler maintains — slot table, commit limits, prefill cursors,
    latency samples, and the staged-retirement mask. With ``dp=1`` there
    is exactly one of these and the scheduler degenerates to the
    single-replica behaviour."""

    def __init__(self, rep: int, dec: SpecDecoder, ex: Executor,
                 alloc: Optional[kv_pool.BlockAllocator], max_batch: int):
        self.rep = rep
        self.base = rep * max_batch     # TreeController slot-row offset
        self.dec, self.ex, self.alloc = dec, ex, alloc
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.slot_limit = np.zeros(max_batch, np.int64)
        self.slot_tree = np.zeros(max_batch, np.int32)
        self.slot_steps = np.zeros(max_batch, np.int64)
        # host mirrors of the device prefill cursor (advanced in lockstep)
        self.slot_pf = np.zeros(max_batch, np.int64)
        self.slot_pf_len = np.zeros(max_batch, np.int64)
        # latency accounting
        self.slot_submit_t = np.zeros(max_batch)
        self.slot_admit_t = np.zeros(max_batch)
        self.slot_first_t = np.full(max_batch, np.nan)
        self.slot_last_t = np.zeros(max_batch)
        self.slot_last_n = np.zeros(max_batch, np.int64)
        self.slot_samples: List[List] = [[] for _ in range(max_batch)]
        # staged mutation protocol (DESIGN.md §9): decisions made while a
        # step may be in flight are applied at the NEXT dispatch boundary
        self.pending_retire = np.zeros(max_batch, bool)
        self._occ_cache: Optional[np.ndarray] = None

    @functools.cached_property
    def kv_group(self) -> int:
        """Pages the target forward's paged attention kernel sweeps at once,
        by the kernels' own rule (0: one a grid step over the whole table,
        as the tree kernel of tree verification does)."""
        if self.dec.tree is not None:
            return 0
        return sweep_group(kv_pool.kernel_pools(self.ex.tc,
                                                self.ex.state.tcache),
                           self.alloc.tables.shape[1])

    def occupied_mask(self) -> np.ndarray:
        """[B] bool — slots holding a live request. Built once per slot
        mutation, not per query: admission and completion invalidate the
        cache; every mask consumer between them shares one array."""
        if self._occ_cache is None:
            self._occ_cache = np.asarray([s is not None for s in self.slots])
        return self._occ_cache

    def live_decode_mask(self) -> np.ndarray:
        """Rows occupied AND past their prefill (the rows a step commits
        tokens for)."""
        return self.occupied_mask() & (self.slot_pf >= self.slot_pf_len)

    def prefilling_count(self) -> int:
        """Occupied rows whose prefill cursor has not reached the prompt."""
        occ = self.occupied_mask()
        return int((occ & (self.slot_pf < self.slot_pf_len)).sum())

    def occupancy(self) -> int:
        """Occupied-slot count — the load metric admission routing uses."""
        return int(self.occupied_mask().sum())

    def first_free_slot(self) -> Optional[int]:
        """Lowest free slot index, or None when the replica is full."""
        for slot, s in enumerate(self.slots):
            if s is None:
                return slot
        return None

    def has_live(self) -> bool:
        """True when any slot holds a request (the replica needs steps)."""
        return any(s is not None for s in self.slots)


class Scheduler:
    """Queues, admission and accounting over one or more Executor replicas
    (see module docstring). The Engine drives ``admit() ->
    dispatch(replica)`` once per tick per live replica and
    ``process(handle)`` once per completed step — back-to-back in the
    synchronous loop, one step apart in the pipelined one."""

    def __init__(self, dec, executor, alloc, *, mode: str,
                 max_batch: int, max_len: int, temperature: float,
                 eos_id: Optional[int], bank: Optional[TemplateBank],
                 ctrl: Optional[TreeController], prefix_cache: bool,
                 admit_window: int, prefill_budget: Optional[int],
                 tree_reselect_every: int,
                 prefix_index: Optional[kv_pool.PrefixIndex] = None):
        """``dec`` / ``executor`` / ``alloc`` are either single objects
        (``dp=1``, the historical form) or equal-length sequences — one
        per data-parallel replica. ``prefix_index`` is the shared
        cross-replica prefix-cache index admission routes over (None for
        a single replica, where routing is a no-op)."""
        exs = list(executor) if isinstance(executor, (list, tuple)) \
            else [executor]
        decs = list(dec) if isinstance(dec, (list, tuple)) \
            else [dec] * len(exs)
        allocs = list(alloc) if isinstance(alloc, (list, tuple)) \
            else [alloc] * len(exs)
        if not (len(decs) == len(exs) == len(allocs)):
            raise ValueError(
                f"replica sequences disagree: {len(decs)} decoders, "
                f"{len(exs)} executors, {len(allocs)} allocators")
        self.replicas = [_Replica(r, d, e, a, max_batch)
                         for r, (d, e, a)
                         in enumerate(zip(decs, exs, allocs))]
        self.dp = len(self.replicas)
        self.prefix_index = prefix_index
        self.dec = decs[0]    # shape config: templates / slack / chunking
        self.mode = mode
        self.max_batch, self.max_len = max_batch, max_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.bank, self.ctrl = bank, ctrl
        self.prefix_cache = prefix_cache
        self.admit_window = admit_window
        self.tree_reselect_every = tree_reselect_every
        self.chunk = self.dec.chunk_width
        # token budget per step for prompt chunks -> concurrent lanes
        # (per REPLICA: the budget protects each replica's own fused step)
        self.prefill_lanes = (None if prefill_budget is None
                              else max(1, prefill_budget // self.chunk))

        self.queue: deque[Request] = deque()
        self.completions: List[Completion] = []
        # per-step host overhead: harvest-complete -> next dispatch, ms.
        # The harvest-complete stamp (perf_counter_ns) survives between
        # ``Engine.run`` calls, so a loop run one step a call still records
        # a sample per step; the engine clears it when the loop drains.
        self.host_overhead_ms: List[float] = []
        self._harvest_done_ns: Optional[int] = None

        self._next_rid = 0
        self._submit_t_of: Dict[int, float] = {}   # rid -> submit wall time
        self.stats: Dict = dict(
            steps=0, committed=0, accepted=0, live_steps=0,
            draft_forwards=0, target_forwards=0, round_hist=None,
            prefill_chunks=0, prefill_tokens=0,
            prefix_lookup_blocks=0, prefix_hit_blocks=0,
            replica_steps=[0] * self.dp, affinity_routed=0,
            # host phases of the loop (serving/spans.py), integer ns
            **spans.counters(),
            # paged KV: pages the occupied rows' contexts cover, and the
            # pages the attention kernels' grid steps over (see dispatch)
            kv_pages_live=0, kv_pages_grid=0,
            # expert rows the MoE layers computed on this device, summed
            # from each step's device count at harvest
            moe_rows=0)
        if bank is not None:
            self.stats["tree_hist"] = np.zeros(len(bank), np.int64)
            self.stats["tree_switches"] = 0

    # ---------------------------------------------- replica-0 conveniences
    # The historical single-replica attribute surface (engine facade,
    # tests, benchmarks) reads through to replica 0; with dp=1 that IS the
    # whole scheduler state.
    @property
    def ex(self) -> Executor:
        """Replica 0's executor (the only one with ``dp=1``)."""
        return self.replicas[0].ex

    @property
    def alloc(self) -> kv_pool.BlockAllocator:
        """Replica 0's block allocator."""
        return self.replicas[0].alloc

    @property
    def slots(self) -> List[Optional[Request]]:
        """Replica 0's slot table."""
        return self.replicas[0].slots

    # ------------------------------------------------------------- submit
    def submit(self, prompt, max_new: Optional[int] = None,
               temperature: Optional[float] = None,
               tree_idx: Optional[int] = None,
               params: Optional[SamplingParams] = None) -> int:
        if params is None:
            params = SamplingParams(max_new=max_new, temperature=temperature,
                                    tree_idx=tree_idx)
        else:
            if temperature is not None or tree_idx is not None:
                raise ValueError("pass per-request options inside "
                                 "SamplingParams, not alongside it")
            params = params.merged(max_new)
        max_new, tree_idx = params.max_new, params.tree_idx
        prompt = np.asarray(prompt, np.int32)
        if tree_idx is not None and (
                self.bank is None or not 0 <= tree_idx < len(self.bank)):
            raise ValueError(
                f"tree_idx={tree_idx} needs a TemplateBank with more "
                f"than {tree_idx} templates")
        if self.bank is None:
            slack = self.dec.window_slack
        elif tree_idx is not None:
            slack = self.dec.row_slack(tree_idx)
        else:
            slack = self.dec.min_row_slack
        need = len(prompt) + max_new + slack
        if len(prompt) < 2 or need > self.max_len:
            # a raised error, not an assert: past this point an oversized
            # request would outgrow its cache rows/blocks and silently
            # attend garbage
            raise ValueError(
                f"request needs {need} cache positions (prompt="
                f"{len(prompt)}, max_new={max_new}, window slack="
                f"{slack}) but max_len={self.max_len}; "
                f"prompts also need >= 2 tokens")
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, prompt, params))
        self._submit_t_of[rid] = time.perf_counter()
        return rid

    def has_work(self) -> bool:
        """True while anything is queued or occupies a slot anywhere."""
        return bool(self.queue) or any(rep.has_live()
                                       for rep in self.replicas)

    def occupied_mask(self) -> np.ndarray:
        """Replica 0's occupancy mask (see ``_Replica.occupied_mask``)."""
        return self.replicas[0].occupied_mask()

    def live_decode_mask(self) -> np.ndarray:
        """Replica 0's live-decode mask (see ``_Replica``)."""
        return self.replicas[0].live_decode_mask()

    def prefilling_count(self) -> int:
        """Prefilling rows across ALL replicas (the lane budget itself is
        enforced per replica inside admission)."""
        return sum(rep.prefilling_count() for rep in self.replicas)

    # ---------------------------------------------------------- admission
    def _feasible_templates(self, req: Request) -> List[int]:
        """Bank templates whose window slack fits ``req`` inside max_len.
        Never empty: submit() validated the smallest slack."""
        budget = self.max_len - len(req.prompt) - req.max_new
        return [t for t in range(len(self.bank))
                if self.dec.row_slack(t) <= budget]

    def _pick_template(self, req: Request) -> int:
        if self.bank is None:
            return 0
        if req.tree_idx is not None:
            return req.tree_idx
        feasible = self._feasible_templates(req)
        if self.ctrl is None:
            return 0 if 0 in feasible else feasible[0]
        return self.ctrl.select(feasible=feasible)

    def _try_admit(self, rep: _Replica, slot: int, req: Request) -> bool:
        """Admit ``req`` into replica ``rep``'s ``slot`` if its resources
        exist right now: KV blocks (after prefix matching against THIS
        replica's pool) and a prefill lane on this replica.
        Returns False without side effects when they don't."""
        p = len(req.prompt)
        tmpl = self._pick_template(req)
        slack = self.dec.row_slack(tmpl) if self.bank is not None \
            else self.dec.window_slack
        need = p + req.max_new + slack

        keys: List[bytes] = []
        hit: List[int] = []
        if self.prefix_cache:
            keys = kv_pool.prefix_block_keys(
                req.prompt, rep.alloc.block_size, kv_dtype=rep.ex.kv_dtype)
            hit = rep.alloc.match_prefix(keys)
        nb = rep.alloc.blocks_needed(need)
        if not rep.alloc.can_allocate(nb - len(hit), hit) \
                and self.bank is not None and req.tree_idx is None:
            # the controller's pick outgrows the pool: serve the request on
            # the narrowest feasible template instead of head-of-line
            # blocking (reshaping can widen it later as completions free
            # blocks); pinned requests keep their shape and wait
            tmpl = min(self._feasible_templates(req), key=self.dec.row_slack)
            need = p + req.max_new + self.dec.row_slack(tmpl)
            nb = rep.alloc.blocks_needed(need)
        if not rep.alloc.can_allocate(nb - len(hit), hit):
            return False                           # memory backpressure
        pf_start = len(hit) * rep.alloc.block_size
        if pf_start < p - 1 and self.prefill_lanes is not None \
                and rep.prefilling_count() >= self.prefill_lanes:
            return False                           # prefill budget exhausted

        now = time.perf_counter()
        if self.prefix_cache:
            rep.alloc.allocate(slot, need, prefix=hit, keys=keys)
        else:
            # plain positional call — tests spy on allocate(slot, n)
            rep.alloc.allocate(slot, need)
        self.stats["prefix_lookup_blocks"] += len(keys)
        self.stats["prefix_hit_blocks"] += len(hit)
        # defensive COW (kv_pool I2): with block-aligned matching the
        # first writable position always lands past the shared prefix,
        # but if a future matching policy maps the boundary block this
        # is what keeps shared KV immutable
        first_write_block = min(pf_start, p - 1) // rep.alloc.block_size
        for i in sorted(rep.alloc.read_only.get(slot, ())):
            if i >= first_write_block:
                pair = rep.alloc.copy_on_write(slot, i)
                if pair is not None:
                    rep.ex.copy_block(*pair)
        t = self.temperature if req.temperature is None else req.temperature
        rep.ex.admit_row(slot, req.prompt, float(t), req.rid, int(tmpl),
                         pf_start, seed=req.seed)
        # admission fully reinitializes the row (the eager admit_row writes
        # enqueue AFTER any in-flight step, so its trailing writes to this
        # slot land first), making a still-staged retire of the previous
        # occupant a stale no-op — it MUST be cancelled or the next
        # dispatch would kill the fresh request
        rep.pending_retire[slot] = False
        rep.slots[slot] = req
        rep._occ_cache = None
        rep.slot_limit[slot] = p + req.max_new
        rep.slot_tree[slot] = tmpl
        rep.slot_steps[slot] = 0
        rep.slot_pf[slot] = pf_start
        rep.slot_pf_len[slot] = p - 1
        rep.slot_submit_t[slot] = self._submit_t_of.pop(req.rid, now)
        rep.slot_admit_t[slot] = now
        rep.slot_first_t[slot] = np.nan
        rep.slot_last_t[slot] = now
        rep.slot_last_n[slot] = p
        rep.slot_samples[slot] = []
        if self.ctrl is not None:
            self.ctrl.seed_slot(rep.base + slot)
        return True

    def _route_order(self, req: Request):
        """Replica visit order for admitting ``req`` — prefix-affinity
        first, then least-loaded (DESIGN.md §12). The replica holding the
        LONGEST computed cached prefix of the prompt goes first (it serves
        the hit copy-free from its own pool); the rest follow by occupancy
        (fewest occupied slots, ties to the lowest id). A preferred
        replica that is full or out of blocks is simply skipped — the
        request falls through to the next candidate instead of stalling.
        Returns ``(replica, hit_blocks)`` pairs."""
        reps = sorted(self.replicas, key=lambda r: (r.occupancy(), r.rep))
        if self.dp == 1 or not self.prefix_cache:
            return [(r, 0) for r in reps]
        keys = kv_pool.prefix_block_keys(
            req.prompt, self.replicas[0].alloc.block_size,
            kv_dtype=self.replicas[0].ex.kv_dtype)
        if not keys:
            return [(r, 0) for r in reps]
        if self.prefix_index is not None:
            hits = {r: len(m)
                    for r, m in self.prefix_index.match(keys).items()}
        else:
            hits = {r.rep: len(r.alloc.match_prefix(keys)) for r in reps}
        order = sorted(reps, key=lambda r: (-hits.get(r.rep, 0),
                                            r.occupancy(), r.rep))
        return [(r, hits.get(r.rep, 0)) for r in order]

    def admit(self) -> int:
        """Fill free slots from a bounded prefix of the queue (FIFO-fair
        skip-ahead): position 0 is always tried first, and a later request
        (within ``admit_window``) may only overtake when every earlier one
        cannot currently fit — so smaller requests flow around a
        pool-oversized head instead of starving behind it, while nothing
        beyond the window ever jumps the line. Each admission is routed
        across replicas by ``_route_order`` (prefix-affinity, then
        least-loaded; with ``dp=1`` the order is trivially [replica 0] and
        this is the historical single-engine admission loop)."""
        with spans.phase(self.stats, "admit") as ph:
            admitted = 0
            progress = True
            while progress and self.queue:
                progress = False
                window = min(len(self.queue), self.admit_window)
                for qi in range(window):
                    req = self.queue[qi]
                    for rep, hit_len in self._route_order(req):
                        slot = rep.first_free_slot()
                        if slot is None:
                            continue       # replica full: fall through
                        if self._try_admit(rep, slot, req):
                            del self.queue[qi]
                            admitted += 1
                            if hit_len > 0:
                                self.stats["affinity_routed"] += 1
                            progress = True
                            break
                    if progress:
                        break              # re-scan from the queue head
            ph.set(admitted=admitted)
        return admitted

    # ----------------------------------------------------------- stepping
    def dispatch(self, replica: int = 0) -> StepHandle:
        """Issue one fused step on ``replica``, non-blocking. The staged
        mutations from every ``process`` since that replica's last
        dispatch (retirements, template re-selections — already mirrored
        in its ``slot_tree``) are applied on device AHEAD of the inner
        step; per-slot commit limits ride along so a row that filled its
        budget in a still-unharvested step freezes itself. All
        dispatch-deterministic accounting advances immediately: the step
        counters, the paged-KV page counts, and the prefill cursor
        mirrors + computed-block flags (the chunk schedule is a pure
        function of the cursor, so admission decisions made while this
        step is in flight see exact cursors)."""
        step = self.stats["steps"]
        with spans.phase(self.stats, "dispatch", step=step) as ph:
            if self._harvest_done_ns is not None:
                self.host_overhead_ms.append(
                    (ph.t0 - self._harvest_done_ns) / 1e6)
                self._harvest_done_ns = None
            rep = self.replicas[replica]
            occ = rep.occupied_mask()
            limits = np.where(occ, rep.slot_limit, NO_LIMIT).astype(np.int64)
            tree_sel = (rep.slot_tree.astype(np.int32, copy=True)
                        if self.bank is not None else None)
            # greedy-specialization hint: retired slots' device temps are
            # zeroed by THIS dispatch's staged mutations before the inner
            # step runs, so occupied host mirrors are exactly the rows whose
            # temp survives — when none samples, the executor picks the
            # compiled variant with the sampled machinery removed
            # (token-identical)
            any_sampled = any(
                s is not None
                and (self.temperature if s.temperature is None
                     else s.temperature) > 0
                for s in rep.slots)
            any_prefilling = rep.prefilling_count() > 0
            handle = rep.ex.dispatch(
                retire=rep.pending_retire, tree_sel=tree_sel, limits=limits,
                any_prefilling=any_prefilling, any_sampled=any_sampled)
            handle.rids = np.asarray(
                [-1 if s is None else s.rid for s in rep.slots], np.int64)
            handle.replica = replica
            handle.step = step
            rep.pending_retire = np.zeros(self.max_batch, bool)

            self.stats["steps"] += 1
            self.stats["replica_steps"][replica] += 1
            self.stats["target_forwards"] += 1
            self.stats["draft_forwards"] += handle.n_draft
            self._count_kv_pages(rep, occ, any_prefilling)
            # advance the host prefill mirrors in lockstep with the device
            for slot in np.nonzero(occ)[0]:
                pf, pfl = rep.slot_pf[slot], rep.slot_pf_len[slot]
                if pf < pfl:
                    cl = int(min(self.chunk, pfl - pf))
                    rep.slot_pf[slot] = pf + cl
                    self.stats["prefill_chunks"] += 1
                    self.stats["prefill_tokens"] += cl
                    if self.prefix_cache:
                        # the blocks become readable once THIS step
                        # completes on device — before any later-dispatched
                        # step could read them through a prefix match
                        # (sequential stream)
                        rep.alloc.mark_computed(slot, int(rep.slot_pf[slot]))
        return handle

    def _count_kv_pages(self, rep: _Replica, occ: np.ndarray,
                        any_prefilling: bool) -> None:
        """Add this step's paged-KV page counts, from host mirrors only.

        ``kv_pages_grid``: the pages the paged attention kernels' sweep
        covers. It copies each row's live pages in groups of P
        (``decode_attention.sweep_group`` on the target pool's pages), so
        it covers each occupied row's live pages rounded up to whole
        groups. Where the target's kernel takes one page a grid step
        (P = 0: a quantized pool, a head dim of 64, tree verification) the
        grid steps over ``max_batch`` x the block table's width.

        ``kv_pages_live``: the pages the target forward's ``kv_len``
        (window start + window width) covers in each occupied row, the
        window starting at the last committed token (``slot_last_n - 1``)
        or at the prefill cursor. Their ratio is the share of the sweep
        that does work. Exact in the synchronous loop; in the pipelined
        loop ``slot_last_n`` is one step stale (it advances at harvest)."""
        if self.mode == "ar":
            width = self.chunk if any_prefilling else 1
        else:
            width = self.dec.verify_width
        prefilling = rep.slot_pf < rep.slot_pf_len
        start = np.where(prefilling, rep.slot_pf, rep.slot_last_n - 1)
        table_w = rep.alloc.tables.shape[1]
        pages = np.minimum(-(-(start + width) // rep.alloc.block_size),
                           table_w)
        self.stats["kv_pages_live"] += int(pages[occ].sum())
        group = rep.kv_group
        self.stats["kv_pages_grid"] += (
            int((-(-pages[occ] // group) * group).sum()) if group
            else self.max_batch * table_w)

    def process(self, handle: StepHandle) -> None:
        """Harvest one in-flight step (ONE batched device transfer) and
        fold its results in: stats + controller from the device-reported
        live mask, then completions, with retirement staged for the next
        dispatch boundary."""
        rep = self.replicas[handle.replica]
        with spans.phase(self.stats, "harvest_wait",
                         step=handle.step) as wait:
            res = rep.ex.harvest(handle)
        self._harvest_done_ns = wait.t1
        with spans.phase(self.stats, "harvest", start_ns=wait.t1):
            self._note_results(rep, handle, res)
            self._harvest_completions(rep, handle, res)

    def _note_results(self, rep: _Replica, handle: StepHandle,
                      res: StepResult) -> None:
        """Result-dependent accounting. ``res.live`` is the mask of rows
        the step actually committed for, computed ON DEVICE from the
        post-mutation pre-step state — the host mirrors cannot stand in
        for it here, because by harvest time they already reflect
        decisions staged for the NEXT step (and a request completed at the
        previous harvest may legitimately run one final in-flight step)."""
        live = res.live
        n_live = int(live.sum())
        self.stats["moe_rows"] += res.moe_rows
        if res.a is not None:
            self.stats["accepted"] += int(res.a.sum())
            self.stats["live_steps"] += n_live
            self.stats["committed"] += int(res.a.sum()) + n_live
            if res.rhist is not None:
                self.stats["round_hist"] = (
                    res.rhist if self.stats["round_hist"] is None
                    else self.stats["round_hist"] + res.rhist)
        else:                                        # mode="ar"
            self.stats["committed"] += n_live
        if self.bank is not None:
            # attribute to the templates the step was DISPATCHED with —
            # slot_tree may hold re-selections staged after that
            np.add.at(self.stats["tree_hist"], handle.tree_sel[live], 1)
        # per-SLOT accounting (step cadence, controller EWMAs) only where
        # the slot still holds the request this step was dispatched for —
        # a re-admitted slot must not inherit the previous occupant's final
        # in-flight step
        cur = np.asarray([-1 if s is None else s.rid for s in rep.slots],
                         np.int64)
        acct = live & (handle.rids == cur)
        rep.slot_steps[acct] += 1
        if self.ctrl is not None and acct.any():
            # controller rows are indexed by GLOBAL slot (replica base +
            # local slot): pad the per-replica step arrays out to the
            # controller's row space (with dp=1 this is the identity)
            g = self.ctrl.slot_p.shape[0]
            b = self.max_batch
            acct_g = np.zeros(g, bool)
            acct_g[rep.base:rep.base + b] = acct
            tree_g = np.zeros(g, np.int32)
            tree_g[rep.base:rep.base + b] = handle.tree_sel
            a_g = np.zeros(g, res.a.dtype)
            a_g[rep.base:rep.base + b] = res.a
            rank_g = np.full((g,) + res.rank.shape[1:], -1, res.rank.dtype)
            rank_g[rep.base:rep.base + b] = res.rank
            self.ctrl.update(acct_g, tree_g, a_g, rank_g)
            self._reshape_slots(rep, acct)

    def _reshape_slots(self, rep: _Replica, live_mask) -> None:
        """Between-windows template re-selection (the adaptive controller).
        Every ``tree_reselect_every`` live steps a slot re-scores the bank
        under its own EWMA statistics and switches when a different
        template wins AND the slot can hold it: within max_len, and
        growable in place (``BlockAllocator.grow``; when the pool is too
        tight the slot just keeps its current shape). Greedy losslessness
        is shape-independent, so reshaping mid-request never changes
        committed tokens' correctness, only how many arrive per step."""
        for slot in np.nonzero(live_mask)[0]:
            req = rep.slots[slot]
            if req is None or req.tree_idx is not None:
                continue            # pinned requests keep their shape
            if rep.slot_steps[slot] % self.tree_reselect_every:
                continue
            best = self.ctrl.select(slot=int(rep.base + slot),
                                    feasible=self._feasible_templates(req))
            if best == int(rep.slot_tree[slot]):
                continue
            need = len(req.prompt) + req.max_new + self.dec.row_slack(best)
            if not rep.alloc.grow(int(slot), need):
                continue            # pool too tight: keep the old shape
            # STAGED: the mirror update is picked up by the next dispatch's
            # tree_sel (no eager device scatter); growing the block table
            # above only ever widens a row, so a still-in-flight step using
            # the old table + old template stays within its allocation
            rep.slot_tree[slot] = best
            self.stats["tree_switches"] += 1

    # ------------------------------------------------------------ harvest
    def _harvest_completions(self, rep: _Replica, handle: StepHandle,
                             res: StepResult) -> None:
        """Detect finished requests from one harvested step's ``n``/``gen``
        (already on host — no extra transfers) and build their
        Completions. Retirement is STAGED (``pending_retire``), applied at
        the next dispatch boundary; the completion's tokens come from THIS
        step's snapshot, so anything a later in-flight step speculates for
        the slot never reaches the output. Block release is immediate and
        safe under the pipeline: an in-flight step's trailing writes for a
        released row land at positions >= its prompt length — never inside
        a prefix-cache-registered (prompt-covered) block — and complete on
        the sequential device stream before any step dispatched after the
        release could read the reused blocks."""
        n_host, gen_host = res.n, res.gen
        now = time.perf_counter()
        for slot, req in enumerate(rep.slots):
            if req is None:
                continue
            if int(handle.rids[slot]) != req.rid:
                # the slot was re-admitted while this step was in flight:
                # the snapshot belongs to the PREVIOUS occupant (already
                # completed) — attributing its n/gen to the new request
                # would instantly "finish" it with someone else's tokens
                continue
            p = len(req.prompt)
            # latency: tokens committed since the last tick
            c = int(n_host[slot] - rep.slot_last_n[slot])
            if c > 0:
                if np.isnan(rep.slot_first_t[slot]):
                    rep.slot_first_t[slot] = now
                rep.slot_samples[slot].append(
                    ((now - rep.slot_last_t[slot]) / c, c))
                rep.slot_last_t[slot] = now
                rep.slot_last_n[slot] = n_host[slot]

            limit = rep.slot_limit[slot]
            end, hit_eos = None, False
            if self.eos_id is not None and n_host[slot] > p:
                row = gen_host[slot, p:n_host[slot]].tolist()
                if self.eos_id in row:
                    # truncate AT the EOS: tokens speculatively committed
                    # after it in the same window are dropped from the
                    # completion (the old engine kept them — ISSUE 5)
                    end = min(p + row.index(self.eos_id) + 1, int(limit))
                    hit_eos = True
            if n_host[slot] >= limit or hit_eos:
                if end is None:
                    end = int(min(n_host[slot], limit))
                samples = rep.slot_samples[slot]
                ttft = (rep.slot_first_t[slot] - rep.slot_submit_t[slot]
                        if not np.isnan(rep.slot_first_t[slot]) else 0.0)
                self.completions.append(Completion(
                    rid=req.rid, tokens=gen_host[slot, :end].copy(),
                    generated=int(end - p),
                    wall_submitted=rep.slot_submit_t[slot],
                    wall_done=now,
                    queue_wait=rep.slot_admit_t[slot]
                    - rep.slot_submit_t[slot],
                    ttft=float(ttft),
                    tok_p50=_weighted_percentile(samples, 50),
                    tok_p95=_weighted_percentile(samples, 95)))
                rep.slots[slot] = None
                rep._occ_cache = None
                rep.slot_pf_len[slot] = 0
                rep.slot_pf[slot] = 0
                rep.pending_retire[slot] = True
                if self.ctrl is not None:
                    self.ctrl.retire_slot(rep.base + slot)
                rep.alloc.release(slot)      # O(1); blocks reusable at once

    # ------------------------------------------------------------ summary
    def mean_accepted(self) -> float:
        """Mean committed tokens per live row per verify step (a + 1) —
        the tree/flat drafting quality metric gated in CI."""
        if not self.stats["live_steps"]:
            return 0.0
        return 1.0 + self.stats["accepted"] / self.stats["live_steps"]

    def prefix_hit_rate(self) -> float:
        """Fraction of looked-up prompt blocks served from the prefix
        cache (0.0 when caching is off or nothing was looked up)."""
        lookups = self.stats["prefix_lookup_blocks"]
        return self.stats["prefix_hit_blocks"] / lookups if lookups else 0.0

    def latency_summary(self) -> Dict[str, float]:
        """Percentiles over harvested completions, in milliseconds, plus
        the per-step host overhead (harvest-complete -> next dispatch) —
        the serial host time the pipeline exists to hide."""
        comps = self.completions

        def pct(vals, q):
            return float(np.percentile(vals, q)) * 1e3 if vals else 0.0

        ttfts = [c.ttft for c in comps]
        waits = [c.queue_wait for c in comps]
        ovh = self.host_overhead_ms
        return dict(
            requests=len(comps),
            queue_wait_p50_ms=pct(waits, 50),
            ttft_p50_ms=pct(ttfts, 50),
            ttft_p95_ms=pct(ttfts, 95),
            tok_p50_ms=_weighted_percentile(
                [(c.tok_p50, max(1, c.generated)) for c in comps], 50) * 1e3,
            tok_p95_ms=_weighted_percentile(
                [(c.tok_p95, max(1, c.generated)) for c in comps], 95) * 1e3,
            host_overhead_p50_ms=(float(np.percentile(ovh, 50))
                                  if ovh else 0.0),
            host_overhead_p95_ms=(float(np.percentile(ovh, 95))
                                  if ovh else 0.0),
        )
