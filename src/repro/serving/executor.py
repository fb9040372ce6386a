"""Device-side half of the serving stack (DESIGN.md §8).

The ``Executor`` owns everything that lives on the accelerator: the KV
pools, the single ``DecodeState`` pytree (built by the same
``SpecDecoder.init_state`` the uniform-batch ``generate_*`` paths use),
and the jitted step functions — the SAME ``SpecDecoder`` step builders,
in which every step advances decoding rows AND consumes prompt chunks for
prefilling rows in one fused forward (no standalone prefill forwards, no
admission stall).

The host-side ``serving.scheduler.Scheduler`` decides WHO runs (queues,
admission, block allocation, template selection, latency accounting); the
executor only moves the device state: row admission writes the prompt into
``gen`` and arms the prefill cursor, retirement freezes the row, and
``sync_tables`` pushes the allocator's host block tables whenever they
change so released rows' stale writes route to the garbage block
(kv_pool I4). ``serving.engine.Engine`` wires the two together and keeps
the public API.

Stepping is split into a non-blocking ``dispatch`` and a blocking
``harvest`` (DESIGN.md §9) so the engine can run a two-deep pipeline:
``dispatch`` enqueues ONE fused XLA computation — staged mutations
(retirement mask, per-row template re-selection, commit-limit freeze)
folded in AHEAD of the inner step — and returns a ``StepHandle`` of device
futures immediately; ``harvest`` materializes every per-step output
``(a, rank, rhist, live, n, gen, moe_rows)`` in a single batched
``jax.device_get``
instead of one transfer per array. The handle's arrays are ordinary jit
OUTPUTS, distinct buffers from the ones inside the returned ``DecodeState``
— donating the state into the next dispatch therefore never invalidates a
still-unharvested handle (the donation invariant §9 relies on).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spec_decode import SpecDecoder
from ..models.config import SSM, ModelConfig, scan_plan
from . import kv_pool, spans

# "no staged commit limit" sentinel: n never reaches int32 max, so the
# device-side freeze ``done |= n >= limits`` is a no-op for these rows
NO_LIMIT = np.int32(np.iinfo(np.int32).max)


@dataclasses.dataclass
class StepHandle:
    """One in-flight step: device futures plus host metadata snapshotted at
    dispatch time. ``a``/``rank``/``rhist`` are None for mode="ar";
    ``tree_sel`` is the host copy of the per-slot template indices the step
    was dispatched with (stats/controller attribution must use THIS, not
    the scheduler's mirrors, which may be re-staged before harvest)."""
    a: Optional[Any]
    rank: Optional[Any]
    rhist: Optional[Any]
    live: Any                 # [B] bool — rows the step commits tokens for
    n: Any                    # [B] post-step committed counts
    gen: Any                  # [B, L] post-step token buffer
    moe_rows: Any             # int32 scalar: expert rows the step computed
    #                           (None without MoE layers)
    n_draft: int
    tree_sel: Optional[np.ndarray] = None
    # scheduler-stamped: rid per slot at dispatch time (-1 = empty). A slot
    # re-admitted while this step was in flight fails the rid match at
    # process time, so the stale step's n/gen are never attributed to the
    # new request (the one-step-stale commit horizon, DESIGN.md §9)
    rids: Optional[np.ndarray] = None
    # scheduler-stamped: which engine replica dispatched this step, so
    # process() harvests and attributes it on the right replica (§12)
    replica: int = 0
    # scheduler-stamped: the step number, so a harvest's span names the
    # dispatch it belongs to (serving/spans.py)
    step: int = -1


@dataclasses.dataclass
class StepResult:
    """Host-materialized ``StepHandle`` (one batched transfer)."""
    a: Optional[np.ndarray]
    rank: Optional[np.ndarray]
    rhist: Optional[np.ndarray]
    live: np.ndarray
    n: np.ndarray
    gen: np.ndarray
    moe_rows: int = 0


def _zero_ssm_rows(cfg: ModelConfig, cache, slot: int):
    """Reset one batch row's SSM/conv states to the init state (zeros).

    Chunked prefill reuses slots in place — there is no per-request prefill
    forward whose fresh one-row state gets scattered in — so a recycled
    slot's recurrent state must be cleared before its first chunk
    (attention KV needs nothing: validity is ``kv_index < kv_len``)."""
    plan = scan_plan(cfg)

    def zero(entry, scanned):
        def one(leaf):
            if scanned:                      # [R, B, ...]
                return leaf.at[:, slot].set(0)
            return leaf.at[slot].set(0)      # [B, ...]
        return jax.tree.map(one, entry)

    return {
        "prefix": [zero(e, False) if s.mixer == SSM else e
                   for s, e in zip(plan.prefix, cache["prefix"])],
        "scan": [zero(e, True) if s.mixer == SSM else e
                 for s, e in zip(plan.period, cache["scan"])],
    }


def _copy_block(cfg: ModelConfig, cache, src: int, dst: int):
    """Copy one pool block's KV ``src -> dst`` across all attention leaves
    (copy-on-write: the caller just remapped a shared block)."""
    plan = scan_plan(cfg)

    def cp(entry, scanned):
        def one(leaf):
            if scanned:                      # [R, NB, bs, ...]
                return leaf.at[:, dst].set(leaf[:, src])
            return leaf.at[dst].set(leaf[src])
        return jax.tree.map(one, entry)

    return {
        "prefix": [cp(e, False) if s.mixer in kv_pool.ATTN_MIXERS else e
                   for s, e in zip(plan.prefix, cache["prefix"])],
        "scan": [cp(e, True) if s.mixer in kv_pool.ATTN_MIXERS else e
                 for s, e in zip(plan.period, cache["scan"])],
    }


class Executor:
    """Owns the DecodeState + cache pools and runs the fused jitted steps."""

    def __init__(self, dec: SpecDecoder, target_cfg: ModelConfig,
                 draft_cfg: Optional[ModelConfig], mode: str, max_batch: int,
                 max_len: int, num_blocks: int, seed: int, mesh=None,
                 replica: int = 0, tp_ruleset: str = "exact"):
        self.dec = dec
        self.mode = mode
        self.tc, self.dc = target_cfg, draft_cfg
        self.max_batch, self.max_len = max_batch, max_len
        self.kv_dtype = dec.kv_dtype         # the pools' storage dtype
        # which serving ruleset the fused steps trace under ("exact" /
        # "throughput" — DESIGN.md §13); salts the jit keys because the two
        # rulesets bake different sharding constraints into the same step
        self.tp_ruleset = tp_ruleset
        # data-parallel serving (DESIGN.md §12): which engine replica this
        # executor backs. Each replica owns its own _step_fns dict, but the
        # id also salts the jit-cache key so a shared cache could never
        # cross-serve two replicas' differently-placed states.
        self.replica = replica
        # sharded serving (DESIGN.md §11): the target KV pools shard their
        # head dim over the mesh's "model" axis, everything else in the
        # DecodeState replicates, and the fused steps pin in/out shardings
        # so donation reuses the sharded buffers tick over tick
        self.mesh = mesh
        self._rng_base = jax.random.PRNGKey(seed)
        self._step_fns = {}
        self._tables_version = -1
        # draft forwards per step are a STATIC property of the mode (pard /
        # tree: one mask-window forward; vsd: k AR forwards; ar: none) — a
        # host constant, never read back from the jit output, so dispatch
        # stays non-blocking
        self._n_draft = 0 if mode == "ar" else (dec.k if mode == "vsd" else 1)

        # empty slots are frozen rows; the step's pools, tables and
        # counters all come from the one DecodeState constructor
        self.state = dec.init_state(max_batch, max_len, num_blocks,
                                    seed=seed,
                                    with_draft=draft_cfg is not None)
        tcache, dcache = self.state.tcache, self.state.dcache
        self.kv_per_block = (
            kv_pool.kv_bytes_per_block(target_cfg, tcache, num_blocks)
            + (kv_pool.kv_bytes_per_block(draft_cfg, dcache, num_blocks)
               if dcache is not None else 0))
        self.kv_capacity = (
            kv_pool.kv_capacity_bytes(target_cfg, tcache)
            + (kv_pool.kv_capacity_bytes(draft_cfg, dcache)
               if dcache is not None else 0))
        if mesh is not None:
            self._state_sh = self._state_shardings()
            self.state = jax.device_put(self.state, self._state_sh)
        else:
            self._state_sh = None

    # ----------------------------------------------------------- sharding
    def _state_shardings(self):
        """NamedSharding pytree matching the DecodeState: target KV pools
        shard KV heads over "model" (paged_cache_specs), the
        DRAFT pools and every other leaf — tokens, counters, PRNG keys,
        block tables — replicate. The draft replicates because it is small
        and its latency-critical window must not pay any cross-device
        traffic; block tables replicate because every device resolves the
        same block indirection (DESIGN.md §11)."""
        from ..sharding import specs as _specs
        mesh = self.mesh
        repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        t_specs = _specs.paged_cache_specs(self.state.tcache, mesh)
        base = jax.tree.map(lambda _: repl, self.state)
        return dataclasses.replace(base,
                                   tcache=_specs.to_named(t_specs, mesh))

    # ------------------------------------------------------------- tables
    def sync_tables(self, alloc: kv_pool.BlockAllocator) -> None:
        """Push the host block tables to the device state when stale. Runs
        before any forward that could consume them, so released rows' stale
        writes always route to the garbage block (kv_pool I4)."""
        if self._tables_version != alloc.version:
            tables = jnp.asarray(alloc.tables)
            if self.mesh is not None:
                # every device resolves the same block indirection: the
                # table is replicated host-side state (DESIGN.md §11)
                tables = jax.device_put(tables, jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()))
            self.state = dataclasses.replace(self.state, tables=tables)
            self._tables_version = alloc.version

    # ---------------------------------------------------------- row admin
    def admit_row(self, slot: int, prompt: np.ndarray, temperature: float,
                  rid: int, tree_idx: int, pf_start: int,
                  seed: Optional[int] = None) -> None:
        """Arm ``slot`` for a new request: prompt into ``gen``, counters to
        the committed state, prefill cursor at ``pf_start`` (``> 0`` when a
        cached prefix already covers the leading blocks). NO device forward
        happens here — the fused steps prefill chunk by chunk. ``seed``
        (SamplingParams.seed) pins the row's PRNG stream to the request
        itself; None derives it from the engine seed and rid (the
        historical behaviour)."""
        p = len(prompt)
        st = self.state
        gen_row = np.zeros((self.max_len,), np.int32)
        gen_row[:p] = prompt
        row_key = (jax.random.fold_in(self._rng_base, rid) if seed is None
                   else jax.random.PRNGKey(int(seed)))
        self.state = dataclasses.replace(
            st,
            gen=st.gen.at[slot].set(jnp.asarray(gen_row)),
            n=st.n.at[slot].set(p),
            m=st.m.at[slot].set(p - 1),
            done=st.done.at[slot].set(False),
            temp=st.temp.at[slot].set(float(temperature)),
            rngs=st.rngs.at[slot].set(row_key),
            tree_idx=(st.tree_idx if st.tree_idx is None else
                      st.tree_idx.at[slot].set(int(tree_idx))),
            pf_pos=st.pf_pos.at[slot].set(int(pf_start)),
            pf_len=st.pf_len.at[slot].set(p - 1),
            tcache=_zero_ssm_rows(self.tc, st.tcache, slot),
            dcache=(None if st.dcache is None else
                    _zero_ssm_rows(self.dc, st.dcache, slot)))

    def retire_row(self, slot: int) -> None:
        # temp resets with the slot: a retired sampled request must not
        # keep forcing later all-greedy batches onto the sampled lax.cond
        # branch (jnp.any(temp > 0))
        self.state = dataclasses.replace(
            self.state, done=self.state.done.at[slot].set(True),
            temp=self.state.temp.at[slot].set(0.0))

    def set_tree_idx(self, slot: int, tree_idx: int) -> None:
        self.state = dataclasses.replace(
            self.state,
            tree_idx=self.state.tree_idx.at[slot].set(int(tree_idx)))

    def copy_block(self, src: int, dst: int) -> None:
        """Device half of allocator.copy_on_write: duplicate one block's KV
        in every pool (target + draft share block indices)."""
        st = self.state
        self.state = dataclasses.replace(
            st, tcache=_copy_block(self.tc, st.tcache, src, dst),
            dcache=(None if st.dcache is None else
                    _copy_block(self.dc, st.dcache, src, dst)))

    # -------------------------------------------------------------- steps
    def _build(self, variant: str, greedy_only: bool = False):
        if self.mode == "ar":
            # two compiled variants: the 1-wide pure-decode window (the
            # AR+ hot path — pad slots would cost real attention compute
            # every step) and the prefill_chunk-wide mixed window, selected
            # per tick by whether any row is actually prefilling
            builder = self.dec._build_ar_step(chunked=variant == "mixed")

            def step(params, state):
                return builder(params, state), None, None, None, None, 0
            return step
        # spec/tree windows already fit the chunk (same shapes either way:
        # the chunk substitution is a few jnp.where selects), so one
        # compiled step serves both pure-decode and mixed ticks
        if self.dec.tree is not None:
            return self.dec._build_tree_step(greedy_only=greedy_only)
        return self.dec._build_spec_step(
            "pard" if self.mode == "pard" else "vsd",
            greedy_only=greedy_only)

    def _build_fused(self, variant: str, apply_tree: bool,
                     greedy_only: bool = False):
        """One XLA dispatch per tick: staged host decisions (retirement,
        template re-selection, commit-limit freeze) fold into the SAME
        computation as the inner step, replacing the eager per-slot
        ``.at[].set`` dispatches the synchronous loop issued between steps.

        The wrapper also computes the LIVE mask (rows the step commits
        tokens for) on the post-mutation, pre-step state and returns it
        with the step outputs — the pipelined scheduler cannot derive it
        from host mirrors, which run one step ahead of unharvested
        results — and re-returns ``n``/``gen`` as explicit outputs so a
        harvest needs no read of the (soon-to-be-donated) state.
        ``_step_fn`` adds the step's ``state.moe_rows`` as one more
        output, for the same reason (None, no output, without MoE
        layers).

        The (target, draft) params are the step's FIRST argument, never
        closure constants: at published widths constants would embed
        gigabytes of weights in the program."""
        inner = self._build(variant, greedy_only)

        def fused(params, state, retire, tree_sel, limits):
            with jax.named_scope(spans.STAGED):
                # staged retirement + the device-side limit freeze: a row
                # whose committed count reached its limit is frozen even if
                # the host has not harvested that result yet (the pipelined
                # loop's one-step-stale horizon must not let it overrun its
                # blocks)
                done = state.done | retire | (state.n >= limits)
                # temp resets with retirement (see retire_row)
                temp = jnp.where(retire, 0.0, state.temp)
                tree_idx = state.tree_idx
                if apply_tree and tree_idx is not None:
                    tree_idx = tree_sel
                st = dataclasses.replace(state, done=done, temp=temp,
                                         tree_idx=tree_idx)
                live = ~(st.done | (st.pf_pos < st.pf_len))
            new_state, a, _hist, rhist, rank, _nd = inner(params, st)
            return new_state, a, rank, rhist, live, new_state.n, new_state.gen
        return fused

    def dispatch(self, retire: Optional[np.ndarray] = None,
                 tree_sel: Optional[np.ndarray] = None,
                 limits: Optional[np.ndarray] = None,
                 any_prefilling: bool = True,
                 any_sampled: bool = True) -> StepHandle:
        """Enqueue one fused prefill+decode step and return immediately
        with a handle of device futures (the jitted call is asynchronous;
        nothing here blocks). ``retire`` [B] bool / ``tree_sel`` [B] int /
        ``limits`` [B] int are the scheduler's staged mutations (None =
        no-op); ``any_prefilling``: host hint selecting the AR window
        variant; ``any_sampled=False``: host hint (no OCCUPIED slot has
        temperature > 0) selecting the greedy-specialized spec/tree step —
        token-identical, with the sampled machinery compiled out. Greedy
        rows never consume their PRNG streams and a sampled row's key is
        freshly (seed, rid)-derived at admission, so alternating between
        the two compiled variants across steps is safe."""
        variant = "mixed" if (any_prefilling and self.mode == "ar") \
            else "decode"
        step_fn = self._step_fn(variant, tree_sel is not None, any_sampled)
        b = self.max_batch
        retire_d = (jnp.zeros((b,), bool) if retire is None
                    else jnp.asarray(retire, bool))
        limits_d = (jnp.full((b,), NO_LIMIT, jnp.int32) if limits is None
                    else jnp.asarray(limits, jnp.int32))
        tree_d = (jnp.zeros((b,), jnp.int32) if tree_sel is None
                  else jnp.asarray(tree_sel, jnp.int32))
        args = (self._params, self.state, retire_d, tree_d, limits_d)
        if self.mesh is not None:
            # trace under the activation mesh + ruleset so the forward's
            # partial/gather_activation hints bake in (§11/§13)
            from ..kernels import ops as _ops
            with _ops.activation_mesh(self.mesh, self.tp_ruleset):
                self.state, a, rank, rhist, live, n, gen, rows = step_fn(
                    *args)
        else:
            self.state, a, rank, rhist, live, n, gen, rows = step_fn(*args)
        return StepHandle(a=a, rank=rank, rhist=rhist, live=live, n=n,
                          gen=gen, moe_rows=rows, n_draft=self._n_draft,
                          tree_sel=None if tree_sel is None
                          else np.asarray(tree_sel))

    def harvest(self, handle: StepHandle) -> StepResult:
        """Materialize one in-flight step's outputs in a SINGLE batched
        host transfer (blocks until that step completes on device). Safe
        to call after later dispatches: the handle's arrays are distinct
        jit-output buffers, untouched by the state donation."""
        if handle.a is None:                          # mode="ar"
            live, n, gen, rows = jax.device_get(
                (handle.live, handle.n, handle.gen, handle.moe_rows))
            return StepResult(None, None, None, np.asarray(live),
                              np.asarray(n), np.asarray(gen),
                              0 if rows is None else int(rows))
        a, rank, rhist, live, n, gen, rows = jax.device_get(
            (handle.a, handle.rank, handle.rhist, handle.live, handle.n,
             handle.gen, handle.moe_rows))
        return StepResult(np.asarray(a), np.asarray(rank),
                          np.asarray(rhist), np.asarray(live),
                          np.asarray(n), np.asarray(gen),
                          0 if rows is None else int(rows))

    def step(self, any_prefilling: bool = True):
        """One SYNCHRONOUS fused step (dispatch + immediate harvest, no
        staged mutations): the depth-1 special case, kept for tests and
        callers outside the engine's pipeline. Returns host copies of the
        per-row accepted depths / sibling ranks (None for mode="ar") and
        the draft-forward count."""
        handle = self.dispatch(any_prefilling=any_prefilling)
        res = self.harvest(handle)
        if res.a is None:
            return None, None, None, 0
        return res.a, res.rank, res.rhist, handle.n_draft

    def step_hlo(self, *, tree: bool = False, any_sampled: bool = False) -> str:
        """Compiled (post-GSPMD) HLO text of the decode-variant fused step.

        Lowers the SAME jitted step that ``dispatch`` runs against the live
        DecodeState — nothing executes and nothing is donated, so this is
        safe between ticks, and after the step has run it costs no second
        compile (JAX's compilation cache holds the executable).
        tools/comm_audit.py walks the returned text to count per-step
        collectives and their byte volumes (DESIGN.md §13); on a TPU a
        ``tpu_custom_call`` in it shows the Pallas kernels are in the
        step."""
        b = self.max_batch
        args = (self._params, self.state, jnp.zeros((b,), bool),
                jnp.zeros((b,), jnp.int32),
                jnp.full((b,), NO_LIMIT, jnp.int32))
        jitted = self._step_fn("decode", tree, any_sampled)
        if self.mesh is None:
            return jitted.lower(*args).compile().as_text()
        from ..kernels import ops as _ops
        with _ops.activation_mesh(self.mesh, self.tp_ruleset):
            return jitted.lower(*args).compile().as_text()

    @property
    def _params(self):
        """(target, draft) weights: the first argument of every step."""
        return self.dec.tp, self.dec.dp

    def _step_fn(self, variant: str, apply_tree: bool, any_sampled: bool):
        """The jitted fused step for one variant, built once per executor.
        On a mesh the shardings are pinned on BOTH sides: the donated
        state's buffers keep their layout tick over tick (no resharding
        churn), and the step stays one device computation per dispatch.
        Params keep the shardings they were placed with; scalars/handle
        outputs replicate; None outputs (mode="ar") take a None entry."""
        greedy_only = not any_sampled and self.mode != "ar"
        key = (variant, apply_tree, greedy_only, self.kv_dtype,
               self.replica, self.tp_ruleset)
        if key in self._step_fns:
            return self._step_fns[key]
        inner = self._build_fused(variant, apply_tree=apply_tree,
                                  greedy_only=greedy_only)

        def fused(params, state, retire, tree_sel, limits):
            # the step's expert-row count, also as an output of its own
            out = inner(params, state, retire, tree_sel, limits)
            return out + (out[0].moe_rows,)

        if self.mesh is None:
            fn = jax.jit(fused, donate_argnums=(1,))
        else:
            repl = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            aux = repl if self.mode != "ar" else None
            param_sh = jax.tree.map(lambda x: x.sharding, self._params)
            fn = jax.jit(
                fused, donate_argnums=(1,),
                in_shardings=(param_sh, self._state_sh, repl, repl, repl),
                out_shardings=(self._state_sh, aux, aux, aux,
                               repl, repl, repl, repl))
        self._step_fns[key] = fn
        return fn

    # --------------------------------------------------------------- host
    def read_n(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.state.n))

    def read_gen(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.state.gen))
