"""Speculative decoding: AR baseline, vanilla SD (AR draft), PARD.

All step functions use fixed shapes (jit-once):

  * the generation buffer ``gen [B, L]`` holds committed tokens; ``n [B]``
    counts them. Commits write a full (K+1)-slot window at offset n — slots
    beyond the accepted count hold garbage that is overwritten before it can
    ever be read (reads are always < n).
  * KV caches are block-paged pools (serving/kv_pool.py, DESIGN.md §5);
    speculative rollback = the next call's ``cache_pos`` simply re-covers
    the rejected entries (validity is ``index < cache_pos + q_len``, so
    stale KV is invisible).
  * SSM/hybrid layers cannot roll back by position: the verify forward runs
    with ``collect_ssm=True`` and the engine gathers the per-token state at
    the last accepted index (DESIGN.md §3).

PARD draft (paper Eq. 7): ONE forward of
  [ new committed tokens (A <= K+1) | mask x (K-1) | pad ]   (2K slots)
produces all K proposals: slot A-1 (last real token) proposes token 1, the
K-1 mask slots propose the rest. Plain causal attention over this window
equals the paper's mask-token factorisation because exactly one chain is in
flight at inference time.

VSD draft: the same window advances the committed tokens, then K-1 extra
single-token AR calls — K draft forwards/iteration vs PARD's 1 (Eq. 3 vs 4).

Tree drafting (``TreeTemplate`` / ``TemplateBank``): instead of keeping
only the per-depth argmax chain, the SAME single draft forward populates a
static top-k candidate tree (top-b_d tokens at depth d), and verification
runs one target forward over the packed tree with ancestor-mask attention
(kernels/tree_attention.py, DESIGN.md §6). Greedy verification commits the
longest root path matching the target argmax — still exactly lossless vs
AR — and raises accepted tokens per target forward whenever the target's
argmax lands in the draft's top-b_d but not its top-1. The tree shape is
PER ROW (DESIGN.md §7): ``DecodeState.tree_idx`` selects each row's
template from a ``TemplateBank`` inside the one jitted step, so a batch
mixes chains and wide trees and the serving engine reshapes a request
between windows from its acceptance statistics.

Greedy (temperature 0) verification is exactly lossless vs AR decoding.
Temperature > 0 is PER ROW (``DecodeState.temp``; one batch mixes greedy
and sampled requests): the flat chain uses Leviathan speculative sampling
and the tree uses multi-round recursive rejection sampling over sibling
candidates — both in core/acceptance.py, both provably committing tokens
from the target model's own sampling distribution. Sampling draws come from
per-row PRNG keys (``DecodeState.rngs``), so a request's output depends
only on its own seed and step count, never on batch composition or how
its prompt was prefilled (the seeded-determinism tests in
tests/test_sampled_tree.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import KV_BLOCK, blocks_for, forward, init_caches
from ..models.attention import (TreeAttnInfo, paged_index,
                                resolve_kv_dtype)
from ..models.config import (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, MLP_MOE,
                             SSM, ModelConfig, scan_plan)
from ..serving import spans
from . import acceptance

# re-exported: the flat T>0 acceptance rule lives in core/acceptance.py now
speculative_accept = acceptance.speculative_accept

Array = jax.Array

_ATTN_MIXERS = (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA)


def _row_write(buf: Array, vec: Array, pos: Array) -> Array:
    """buf: [B, L]; vec: [B, W]; pos: [B] -> buf with vec written at pos."""
    return jax.vmap(lambda b, v, p: jax.lax.dynamic_update_slice(b, v, (p,)))(
        buf, vec, pos)


def gather_ssm_states(cfg: ModelConfig, collected, accept_idx: Array):
    """Select per-token SSM states at the last accepted index.

    ``collected`` is the new_caches pytree from a ``collect_ssm`` forward:
    SSM entries hold per-token states (conv: [B, T, W-1, C], ssm:
    [B, T, H, P, N]; scanned layers carry a leading repeats dim) while
    attention entries are normal caches. Returns the cache pytree with every
    SSM state set to the state after ``accept_idx[b]`` input tokens.
    """
    plan = scan_plan(cfg)

    def row_gather(leaf):       # [B, T, ...] -> [B, ...]
        return jax.vmap(lambda r, i: jax.lax.dynamic_index_in_dim(
            r, i, 0, False))(leaf, accept_idx)

    def pick(tree, scanned: bool):
        def gather_leaf(leaf):
            if scanned:         # [R, B, T, ...]
                return jax.vmap(row_gather)(leaf)
            return row_gather(leaf)
        return jax.tree.map(gather_leaf, tree)

    out = {"prefix": [], "scan": []}
    for i, spec in enumerate(plan.prefix):
        c = collected["prefix"][i]
        out["prefix"].append(pick(c, False) if spec.mixer == SSM else c)
    for j, spec in enumerate(plan.period):
        c = collected["scan"][j]
        out["scan"].append(pick(c, True) if spec.mixer == SSM else c)
    return out


def _draft_window(gen, n, m, k, mask_id):
    """[B, 2K] PARD draft window: new committed tokens + mask chain."""
    i = jnp.arange(2 * k)[None, :]
    idx = m[:, None] + i
    a = (n - m)[:, None]                          # committed, unprocessed
    tok = jnp.take_along_axis(gen, jnp.clip(idx, 0, gen.shape[1] - 1),
                              axis=1)
    is_real = i < a
    is_mask = (i >= a) & (i < a + (k - 1))
    tok = jnp.where(is_real, tok, jnp.where(is_mask, mask_id, 0))
    return tok.astype(jnp.int32)


def _chunk_window(gen, pf, cl, width):
    """[B, width] prompt chunk starting at the prefill cursor ``pf``; slots
    past the per-row real count ``cl`` are zero pads whose KV writes land
    beyond the cursor and are re-covered by the next chunk / first decode
    window (the standard rollback invariant). ``pf + width`` never reaches
    the buffer end: the engine validates prompt + max_new + window slack
    <= max_len and width <= slack, so the dynamic slice never clamps."""
    tok = jax.vmap(lambda g, p: jax.lax.dynamic_slice(g, (p,), (width,)))(
        gen, pf)
    return jnp.where(jnp.arange(width)[None, :] < cl[:, None], tok,
                     0).astype(jnp.int32)


def _phase(state: "DecodeState"):
    """(prefilling [B], pf [B]) from the state's prefill cursor fields."""
    return state.pf_pos < state.pf_len, state.pf_pos


def _pick_next(logits: Array, temp: Array, keys: Array) -> Array:
    """[B, V] logits -> [B] next token: argmax for temp == 0 rows, a sample
    from softmax(logits / temp) under the row's own key otherwise. The
    sampling branch only executes when some row actually samples, so
    all-greedy batches pay nothing for it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def samp():
        s = acceptance.row_categorical(
            keys, acceptance.scale_logits(logits, temp))
        return jnp.where(temp > 0, s, greedy)

    return jax.lax.cond(jnp.any(temp > 0), samp, lambda: greedy)


def _topk_indices(logits: Array, k: int) -> Array:
    """Indices of the ``k`` largest logits along the last axis, descending,
    lowest-index tie-break — exactly ``lax.top_k``'s order — via ``k``
    argmax-and-mask passes. XLA:CPU lowers ``top_k`` to a full sort of the
    vocab axis (the single most expensive op in a tree step on small
    models); for the tiny branching factors trees use, a few fused reduce
    passes are far cheaper on every backend."""
    idx = []
    cur = logits
    ar = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    for j in range(k):
        i = jnp.argmax(cur, axis=-1).astype(jnp.int32)
        idx.append(i)
        if j + 1 < k:
            cur = jnp.where(ar == i[..., None], -jnp.inf, cur)
    return jnp.stack(idx, axis=-1)


def _has_ssm(cfg: ModelConfig) -> bool:
    plan = scan_plan(cfg)
    return any(s.mixer == SSM for s in plan.prefix + plan.period)


def _has_moe(cfg: ModelConfig) -> bool:
    plan = scan_plan(cfg)
    return any(s.mlp == MLP_MOE for s in plan.prefix + plan.period)


# ---------------------------------------------------------------------------
# Candidate trees — static templates for tree-structured PARD drafting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TreeTemplate:
    """Static top-k candidate tree for PARD tree drafting (DESIGN.md §6).

    Built from per-depth branching factors: every node at depth d-1 expands
    into one child per top-k rank c < branching[d-1] of the draft's depth-d
    proposal distribution. PARD's mask-chain draft yields ONE distribution
    per depth (conditioning is on the mask chain, not the sampled branch),
    so siblings across different parents share candidate tokens — but each
    node needs its own slot because the target's verification logits DO
    condition on the actual path.

    Slot 0 is the root (the re-processed last committed token); nodes are
    laid out breadth-first, so a node's parent always precedes it. The whole
    window (1 + num_nodes slots) must fit a uint32 ancestor bitmask: <= 32.
    """
    branching: Tuple[int, ...]
    parent: Any          # np [S] int32; parent[0] = -1
    depth: Any           # np [S] int32; depth[0] = 0
    choice: Any          # np [S] int32; top-k rank at the node's depth
    anc: Any             # np [S] uint32 packed ancestor-or-self bitmask

    @staticmethod
    def from_branching(branching) -> "TreeTemplate":
        """Build the template from per-depth branching factors: every
        node at depth d-1 expands into one child per top-k rank
        ``c < branching[d-1]`` (DESIGN.md §6). Slot 0 is the root; the
        packed ancestor bitmask caps a template at 32 slots."""
        branching = tuple(int(x) for x in branching)
        assert branching and all(x >= 1 for x in branching), branching
        parent, depth, choice = [-1], [0], [0]
        prev, slot = [0], 1
        for d, bd in enumerate(branching, start=1):
            new = []
            for p in prev:
                for c in range(bd):
                    parent.append(p)
                    depth.append(d)
                    choice.append(c)
                    new.append(slot)
                    slot += 1
            prev = new
        assert slot <= 32, (
            f"tree template needs {slot} window slots but the packed "
            f"ancestor bitmask holds 32 (shrink the branching factors)")
        anc = [1]
        for s in range(1, slot):
            anc.append(anc[parent[s]] | (1 << s))
        return TreeTemplate(
            branching=branching,
            parent=np.asarray(parent, np.int32),
            depth=np.asarray(depth, np.int32),
            choice=np.asarray(choice, np.int32),
            anc=np.asarray(anc, np.uint32))

    @staticmethod
    def flat(k: int) -> "TreeTemplate":
        """Degenerate single-branch chain — token-identical to the flat-K
        path (asserted in tests and the serve_tree benchmark)."""
        return TreeTemplate.from_branching((1,) * k)

    @property
    def num_slots(self) -> int:
        """Window slots the packed tree occupies (1 root + num_nodes)."""
        return len(self.parent)          # 1 + num_nodes

    @property
    def num_nodes(self) -> int:
        """Candidate nodes (slots minus the root)."""
        return len(self.parent) - 1

    @property
    def max_depth(self) -> int:
        """Deepest candidate depth — the flat-K analogue of K."""
        return len(self.branching)

    @property
    def is_chain(self) -> bool:
        """True for a single-branch template (the flat-K degenerate)."""
        return all(b == 1 for b in self.branching)


@dataclasses.dataclass(frozen=True)
class TemplateBank:
    """Static bank of candidate-tree templates selectable PER ROW
    (DESIGN.md §7).

    All templates share one depth K (pad branchings with trailing 1s), so
    the single PARD draft window — whose length is 2K — serves every row.
    Slot metadata is padded to the widest template (``max_slots``) and
    stacked, and the jitted tree step gathers each row's arrays by
    ``DecodeState.tree_idx``: one compiled step serves a batch mixing tree
    shapes. Padded slots carry zeroed metadata (anc == 0, depth == 0) and
    are additionally masked by ``nslots``, so they can never be accepted;
    their KV writes land beyond the row's meaningful window and are
    re-covered like any rejected branch.
    """
    templates: Tuple[TreeTemplate, ...]
    parent: Any      # np [T, S] int32 (padded slots 0; slot 0 = -1)
    depth: Any       # np [T, S] int32 (padded slots 0)
    choice: Any      # np [T, S] int32
    anc: Any         # np [T, S] uint32 (padded slots 0)
    child_map: Any   # np [T, S, MB] int32 (0 = absent child)
    nslots: Any      # np [T] int32

    @staticmethod
    def from_templates(templates) -> "TemplateBank":
        """Pack templates (TreeTemplates or raw branching tuples) into
        one bank of stacked per-slot arrays; all templates must share one
        depth so a row can re-select without reshaping the window."""
        templates = tuple(
            t if isinstance(t, TreeTemplate) else
            TreeTemplate.from_branching(t) for t in templates)
        assert templates, "a template bank needs at least one template"
        depths = {t.max_depth for t in templates}
        assert len(depths) == 1, (
            "bank templates must share one depth (pad branchings with "
            f"trailing 1s): {[t.branching for t in templates]}")
        n_t = len(templates)
        s = max(t.num_slots for t in templates)
        mb = max(max(t.branching) for t in templates)
        parent = np.zeros((n_t, s), np.int32)
        depth = np.zeros((n_t, s), np.int32)
        choice = np.zeros((n_t, s), np.int32)
        anc = np.zeros((n_t, s), np.uint32)
        cmap = np.zeros((n_t, s, mb), np.int32)
        for i, t in enumerate(templates):
            ns = t.num_slots
            parent[i, :ns] = t.parent
            depth[i, :ns] = t.depth
            choice[i, :ns] = t.choice
            anc[i, :ns] = t.anc
            cm = acceptance.tree_child_map(t)
            cmap[i, :ns, :cm.shape[1]] = cm
        return TemplateBank(
            templates=templates, parent=parent, depth=depth, choice=choice,
            anc=anc, child_map=cmap,
            nslots=np.asarray([t.num_slots for t in templates], np.int32))

    @staticmethod
    def default(k: int = 4) -> "TemplateBank":
        """The canonical three-shape bank at depth ``k``: a flat-K chain
        (deep, no hedging), a balanced tree and a shallow-wide tree — the
        shapes the adaptive controller arbitrates between. Widths shrink
        until the 32-slot window cap admits them, and each later shape
        must also fit the padded window the earlier picks established:
        the bank pads every template's slot metadata to the widest
        member, so a wide hedge that overruns the balanced tree's slot
        count would tax EVERY adaptive step with padded verify slots
        even when the controller never selects it (at k=4 this picks
        `(3,2,1,1)`, 22 slots, over `(4,2,1,1)`, 29)."""
        def nslots(br):
            slots, width = 1, 1
            for x in br:
                width *= x
                slots += width
            return slots

        shapes, cap = [(1,) * k], 32
        for heads in [[(2, 2, 2), (2, 2), (2,)],
                      [(4, 2), (3, 2), (3,), (2, 2, 2), (2, 2)]]:
            for head in heads:
                br = (head + (1,) * (k - len(head)))[:k]
                if len(head) <= k and nslots(br) <= cap and br not in shapes:
                    shapes.append(br)
                    cap = min(cap, nslots(br))
                    break
        return TemplateBank.from_templates(shapes)

    def __len__(self) -> int:
        return len(self.templates)

    @property
    def max_depth(self) -> int:
        """The bank's single shared template depth."""
        return self.templates[0].max_depth

    @property
    def max_slots(self) -> int:
        """Widest template's slot count — the packed window width."""
        return int(self.parent.shape[1])

    @property
    def max_branching(self) -> int:
        """Widest per-depth branching across the bank (child-map width)."""
        return int(self.child_map.shape[2])

    @property
    def key(self) -> str:
        """Stable id for jit caches / labels."""
        return "|".join("x".join(map(str, t.branching))
                        for t in self.templates)


def compact_tree_caches(cfg: ModelConfig, caches, src_pos, dst_start, depth,
                        tables, block_size):
    """Copy the winning tree path's KV onto the committed positions.

    A tree-verification forward writes the window's KV at per-node cache
    slots ``win_start + s``; the accepted path's slots are generally
    non-contiguous — whether greedy argmax-matching or multi-round sampled
    acceptance picked it (``src_pos`` is acceptance-agnostic: slot of the
    accepted node per depth, identity copy for rejected depths).
    Compaction makes the committed prefix contiguous again:
    for d = 1..depth the entry at ``src_pos[:, d-1]`` is copied to position
    ``dst_start + d - 1`` (rejected depths carry src == dst, an identity
    copy; sources never precede their destination, and the gather completes
    before the scatter). Losing branches' slots land beyond the new
    committed count and are re-covered by the next window's ``cache_pos`` —
    the same rollback invariant as the flat path (kv_pool I4 routes frozen
    rows' copies to the garbage block).

    Touches attention pools only; SSM states cannot appear under a tree
    target (positional rollback is a precondition, see _build_tree_step).
    """
    plan = scan_plan(cfg)
    dst_pos = dst_start[:, None] + jnp.arange(depth, dtype=jnp.int32)[None]

    def move(leaf, scanned):         # [(L,) NB, bs, ...]
        # every layer of a stacked pool in one scatter, indexed as
        # paged_index says: vmapped over the layer axis, the scatter is
        # lowered around a relayout of the whole pool
        heads = leaf.shape[-2] if leaf.ndim - scanned == 4 else None
        src = paged_index(tables, src_pos, block_size, heads)
        dst = paged_index(tables, dst_pos, block_size, heads)
        if scanned:
            layer = jnp.arange(leaf.shape[0]).reshape(
                (-1,) + (1,) * src[0].ndim)
            src, dst = (layer,) + src, (layer,) + dst
        return leaf.at[dst].set(leaf[src])

    def one(spec, entry, scanned):
        if spec.mixer not in _ATTN_MIXERS:
            return entry
        return jax.tree.map(lambda x: move(x, scanned), entry)

    return {
        "prefix": [one(s, caches["prefix"][i], False)
                   for i, s in enumerate(plan.prefix)],
        "scan": [one(s, caches["scan"][j], True)
                 for j, s in enumerate(plan.period)],
    }


# ---------------------------------------------------------------------------
# Decode state — the unified core shared with the serving engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeState:
    """Everything one decode step reads and writes, as one pytree.

    Both ``SpecDecoder.generate_*`` (uniform batch, run-to-completion) and
    the continuous-batching serving engine (ragged slots, admission /
    release between steps) build it with ``SpecDecoder.init_state`` and
    advance it through the SAME jitted step functions
    (``SpecDecoder._build_ar_step`` / ``_build_spec_step`` /
    ``_build_tree_step``).

      gen    [B, L]  committed tokens (prompt + generated)
      n      [B]     committed count (reads are always < n)
      m      [B]     draft progress: committed tokens already processed by
                     the draft (n - m = the new-token window)
      done   [B]     frozen rows — steps rewrite their gen/n/m unchanged
      tcache, dcache cache pytrees: attention pools [(L,) NB, bs, ...],
                     SSM states per row (models.init_caches)
      tables [B, MBS] block tables into the pools (DESIGN.md §5); shared
                     by target and draft since both cache the same
                     absolute positions.
      temp   [B]     per-row sampling temperature (0 = greedy; one batch
                     mixes greedy and sampled requests)
      rngs   [B, 2]  per-row PRNG keys — each step splits every row's key
                     once, so a row's sampling stream depends only on its
                     own seed and its step count (seeded determinism across
                     batch compositions).
      tree_idx [B]   per-row template index into the decoder's
                     ``TemplateBank`` (None when tree drafting is off): the
                     tree step gathers each row's packed tree metadata by
                     this index, so one batch mixes tree shapes and the
                     serving engine's adaptive controller reshapes a
                     request between windows by a single scatter.
      pf_pos [B]     chunked-prefill cursor: prompt tokens already written
                     to the KV caches. A row with ``pf_pos < pf_len`` is in
                     the PREFILLING phase: the step builders feed it
                     prompt chunks instead of draft/verify windows inside
                     the SAME jitted forward as the decoding rows
                     (DESIGN.md §8), commit nothing for it, and advance the
                     cursor on device. ``pf_pos == pf_len`` = decoding.
      pf_len [B]     prompt tokens the row must prefill (prompt length - 1:
                     the last prompt token is re-processed by the first
                     verify window, exactly like the uniform-batch prefill).
      moe_rows []    int32: the expert rows the last step's forwards
                     computed on this device (``forward``'s aux); None
                     when neither model has a MoE layer, so that their
                     step carries no count.
    """
    gen: Array
    n: Array
    m: Array
    done: Array
    tcache: Any
    dcache: Any = None
    tables: Optional[Array] = None
    temp: Optional[Array] = None
    rngs: Optional[Array] = None
    tree_idx: Optional[Array] = None
    pf_pos: Optional[Array] = None
    pf_len: Optional[Array] = None
    moe_rows: Optional[Array] = None


# every field is pytree data (derived from the dataclass so new fields can
# never silently fall out of the jitted steps)
jax.tree_util.register_dataclass(
    DecodeState, [f.name for f in dataclasses.fields(DecodeState)], [])


def row_tables(batch: int, n_tokens: int, max_len: int, block_size: int):
    """Pages for ``n_tokens`` positions in each of ``batch`` rows, as a
    fresh serving allocator (kv_pool.BlockAllocator) hands them out row
    after row: block 0 the garbage block, row r the next ``per_row`` pages,
    one owner a page — the block tables of a uniform batch.
    Returns (num_blocks, tables [batch, blocks_for(max_len)] int32)."""
    per_row = blocks_for(n_tokens, block_size)
    tables = np.zeros((batch, blocks_for(max_len, block_size)), np.int32)
    tables[:, :per_row] = 1 + np.arange(batch * per_row).reshape(batch, -1)
    return batch * per_row + 1, jnp.asarray(tables)


def prefill_row(params, cfg: ModelConfig, toks: Array, caches, *, tables,
                block_size, enc_out=None):
    """Prefill ``toks`` [B, T] (every token real) into the pools ``caches``
    through the block ``tables`` at positions 0..T-1: the cache after the
    prompt, SSM states after its last token."""
    _, cache, _ = forward(params, cfg, toks, caches=caches,
                          cache_pos=jnp.zeros((toks.shape[0],), jnp.int32),
                          block_tables=tables, kv_block_size=block_size,
                          enc_out=enc_out, last_only=True)
    return cache


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecStats:
    """Aggregate statistics for one ``generate_*`` run: forward counts,
    acceptance histogram/rates, and wall-clock — the numbers the
    benchmarks and the paper's tables report."""

    iterations: int
    tokens_generated: int
    draft_forwards: int
    target_forwards: int
    accept_hist: Any          # [K] — how often draft position j was accepted
    acceptance_rate: float    # mean accepted drafts / K per iteration
    mean_accepted: float      # mean committed tokens per iteration (a+1)
    round_hist: Any = None    # [max_b] — accepts per sibling rank (tree:
    #                           multi-round rounds / top-k ranks; chain: [1])
    # sharded serving only (tools/comm_audit.py, DESIGN.md §13): per-step
    # collective op counts and byte volumes of the compiled fused step —
    # {"all-reduce": n, ...} / total bytes moved. None off-mesh.
    collective_counts: Any = None
    collective_bytes_per_step: Any = None


class SpecDecoder:
    """Bundles target + draft and exposes AR / VSD / PARD generation.

    All public ``generate_*`` methods take ``prompt [B, P]`` (uniform length;
    the batched serving engine in serving/engine.py handles ragged requests)
    and return (tokens [B, P + max_new], SpecStats). Every cache is a pool
    of ``kv_block_size``-token pages (DESIGN.md §5): ``generate_*`` takes
    its rows' block tables from the serving allocator exactly as the
    engine's executor does.
    """

    def __init__(self, target_params, target_cfg: ModelConfig,
                 draft_params=None, draft_cfg: ModelConfig = None, *,
                 k: int = 8, max_len: int = 2048, temperature: float = 0.0,
                 enc_out=None, draft_enc_out=None, kv_block_size: int = KV_BLOCK,
                 tree: Optional[TreeTemplate] = None,
                 prefill_chunk: int = 8, kv_dtype: str = "bf16",
                 mesh=None, tp_ruleset: str = "exact"):
        self.tp, self.tc = target_params, target_cfg
        self.dp, self.dc = draft_params, draft_cfg
        # sharded serving (DESIGN.md §11/§13): the target is tensor-parallel
        # over the mesh's "model" axis under the selected serving ruleset
        # ("exact" = reduction-free output-dim rules, "throughput" =
        # row-parallel down-projections); the draft replicates (it is
        # small, and replicating avoids any cross-device work inside the
        # latency-critical draft window).
        self.mesh = mesh
        self.tp_ruleset = tp_ruleset
        if mesh is not None:
            from ..sharding import specs as _specs
            self.tp = jax.device_put(
                self.tp,
                _specs.to_named(
                    _specs.param_specs(self.tp, mesh, serving=True,
                                       ruleset=tp_ruleset), mesh))
            if self.dp is not None:
                self.dp = jax.device_put(
                    self.dp,
                    _specs.to_named(_specs.replicated_specs(self.dp), mesh))
        if tree is not None:
            # normalise: branching iterable / TreeTemplate / TemplateBank
            # all become a TemplateBank — ONE tree-step implementation
            # serves static single-template and per-row adaptive decoding
            if not isinstance(tree, TemplateBank):
                if not isinstance(tree, TreeTemplate):
                    tree = TreeTemplate.from_branching(tree)
                tree = TemplateBank.from_templates((tree,))
            if _has_ssm(target_cfg):
                raise NotImplementedError(
                    "tree verification relies on positional KV rollback; "
                    "an SSM/hybrid target cannot roll back a packed tree "
                    "window (DESIGN.md §6)")
            # the draft window must produce one proposal distribution per
            # tree depth: K is the bank's depth, whatever was passed
            k = tree.max_depth
        self.tree: Optional[TemplateBank] = tree
        self.k = k
        self.max_len = max_len
        self.temperature = temperature
        self.enc_out = enc_out
        self.draft_enc_out = draft_enc_out
        # tokens per pool page; steps read and write the pools through the
        # block tables carried in DecodeState.tables
        assert kv_block_size >= 1, "kv_block_size must be >= 1"
        self.kv_block_size = kv_block_size
        # window width of the chunked AR step (engine mode="ar" only; spec
        # and tree chunk widths are bounded by the draft/verify windows —
        # see chunk_width)
        self.prefill_chunk = prefill_chunk
        # KV cache storage dtype ("bf16"/"fp32"/"int8"/"fp8"); quantized
        # dtypes add *_scale cache leaves and change step pytree structure,
        # so it participates in the jit-cache key (_fn)
        self.kv_dtype = kv_dtype
        if draft_cfg is not None:
            assert draft_cfg.vocab_size == target_cfg.vocab_size, \
                "speculative decoding requires a shared tokenizer/vocab"
        # DecodeState.moe_rows is carried only where a MoE layer counts it
        self.counts_moe_rows = _has_moe(target_cfg) or (
            draft_cfg is not None and _has_moe(draft_cfg))
        self._jit_cache: Dict[str, Any] = {}

    @property
    def verify_width(self) -> int:
        """Tokens in the target's verify window: K + 1 flat, the bank's
        widest template for a tree (the window is batch-wide)."""
        return self.tree.max_slots if self.tree is not None else self.k + 1

    @property
    def window_slack(self) -> int:
        """Positions a step may touch beyond the committed count: the 2K
        draft mask window vs the verify window (K+1 flat, the bank's widest
        template for a tree), +2 slack. Sizes ``generate_spec``'s rows; the
        engine allocates per request via ``row_slack`` instead (I3). AR
        decoders (no draft) additionally
        cover the chunked AR step's window: its decode rows carry
        ``prefill_chunk - 1`` pad slots whose KV writes land past the
        committed count and are re-covered next step."""
        slack = max(2 * self.k, self.verify_width)
        if self.dp is None:
            slack = max(slack, self.prefill_chunk)
        return slack + 2

    @property
    def chunk_width(self) -> int:
        """Prompt tokens one chunked engine step consumes per prefilling
        row (DESIGN.md §8). A single cursor feeds BOTH models, so the
        chunk is bounded by the narrower of the 2K draft mask window and
        the target verify window (K+1 flat / bank max_slots tree); AR
        engines have no draft forward and use ``prefill_chunk``."""
        if self.dp is None:
            return self.prefill_chunk
        return min(2 * self.k, self.verify_width)

    def row_slack(self, tmpl_idx: int) -> int:
        """Window slack for ONE request pinned to bank template
        ``tmpl_idx``: its own verify window instead of the bank-wide
        widest. Paged allocations sized with this still satisfy I3 — the
        batch writes the widest window, but a row's writes past its own
        template land in the garbage block and are never read (the row's
        ancestor masks and acceptance only cover its own slots)."""
        assert self.tree is not None, "row_slack applies to tree drafting"
        return max(2 * self.k, int(self.tree.nslots[tmpl_idx])) + 2

    @property
    def min_row_slack(self) -> int:
        """The smallest per-request slack any bank template needs (the
        admission feasibility bound for ``Engine.submit``)."""
        if self.tree is None:
            return self.window_slack
        return min(self.row_slack(i) for i in range(len(self.tree)))

    # -- jitted primitives ------------------------------------------------
    # Every step and prefill takes the weights as its first ARGUMENT,
    # ``params = (target, draft)``, never as closure constants: at
    # published widths constants would embed gigabytes of weights in the
    # compiled program.
    def _fn(self, name, builder, donate=()):
        name = f"{name}@{self.kv_dtype}@{self.tp_ruleset}"
        if name not in self._jit_cache:
            fn = jax.jit(builder, donate_argnums=donate)
            if self.mesh is not None:
                # trace under the activation mesh + ruleset so the
                # forward's partial/gather_activation hints bake into the
                # computation (DESIGN.md §11/§13)
                mesh, ruleset = self.mesh, self.tp_ruleset

                def fn(*a, _jitted=fn, **kw):
                    from ..kernels import ops as _ops
                    with _ops.activation_mesh(mesh, ruleset):
                        return _jitted(*a, **kw)
            self._jit_cache[name] = fn
        return self._jit_cache[name]

    def _target_forward(self, params, tokens, caches, cache_pos, tables,
                        collect_ssm=False, positions=None, tree_info=None):
        return forward(params[0], self.tc, tokens, positions=positions,
                       caches=caches, cache_pos=cache_pos,
                       enc_out=self.enc_out, collect_ssm=collect_ssm,
                       block_tables=tables, kv_block_size=self.kv_block_size,
                       tree_info=tree_info)

    def _draft_forward(self, params, tokens, caches, cache_pos, tables,
                       collect_ssm=False):
        return forward(params[1], self.dc, tokens, caches=caches,
                       cache_pos=cache_pos, enc_out=self.draft_enc_out,
                       collect_ssm=collect_ssm, block_tables=tables,
                       kv_block_size=self.kv_block_size)

    # ----------------------------------------------------------------- AR
    def _build_ar_step(self, chunked: bool = False):
        """One AR decode step over a DecodeState (the AR+ baseline and the
        engine's mode="ar" — one shared implementation). Rows with
        ``state.temp == 0`` commit the argmax; rows with temp > 0 sample
        from softmax(logits / temp) under their own PRNG key.

        Two widths. ``chunked=True`` (the engine's mixed window): the
        window widens to ``prefill_chunk`` slots so PREFILLING rows consume
        prompt chunks in the same forward (DESIGN.md §8). Decoding rows
        carry their last token at slot 0 plus pads whose KV writes land
        past the committed count and are re-covered next step (causal
        masking keeps slot 0's logits exact). ``chunked=False``: the 1-wide
        pure-decode window (``generate_ar`` and the engine's decode-only
        ticks)."""
        w = self.prefill_chunk if chunked else 1

        def step(params, state: DecodeState) -> DecodeState:
            gen, n, done, temp = state.gen, state.n, state.done, state.temp
            next_keys, use = acceptance.split_row_keys(state.rngs)
            last = jnp.take_along_axis(gen, (n - 1)[:, None], axis=1)
            toks = last.astype(jnp.int32)
            cp = n - 1
            if chunked:
                prefilling, pf = _phase(state)
                cl = jnp.minimum(w, state.pf_len - pf)
                toks = jnp.pad(toks, ((0, 0), (0, w - 1)))
                toks = jnp.where(prefilling[:, None],
                                 _chunk_window(gen, pf, cl, w), toks)
                cp = jnp.where(prefilling, pf, cp)
                # sampling streams are untouched while prefilling (see
                # _build_spec_step)
                next_keys = jnp.where(prefilling[:, None], state.rngs,
                                      next_keys)
            with jax.named_scope(spans.TARGET):
                logits, tcache, aux = self._target_forward(
                    params, toks, state.tcache, cp, state.tables)
            with jax.named_scope(spans.ACCEPT):
                nxt = _pick_next(logits[:, 0], temp, use)
            with jax.named_scope(spans.COMMIT):
                gen2 = jax.vmap(lambda g, t, p: jax.lax.dynamic_update_slice(
                    g, t[None], (p,)))(gen, nxt, n)
                frozen = (done | prefilling) if chunked else done
                gen = jnp.where(frozen[:, None], gen, gen2)
                n = jnp.where(frozen, n, n + 1)
                return dataclasses.replace(
                    state, gen=gen, n=n, tcache=tcache, rngs=next_keys,
                    pf_pos=(state.pf_pos if not chunked else
                            jnp.where(prefilling, pf + cl, state.pf_pos)),
                    moe_rows=(None if state.moe_rows is None
                              else aux["moe_rows"]))
        return step

    def init_state(self, batch: int, gen_len: int, num_blocks: int, *,
                   seed: int = 0, with_draft: bool = True) -> DecodeState:
        """The one ``DecodeState`` constructor (``generate_*`` and the
        serving ``Executor``): ``batch`` empty rows — frozen, greedy, every
        table entry on the garbage block, ``n``/``m`` at dummy-safe values —
        over a ``[batch, gen_len]`` token buffer and fresh pools of
        ``num_blocks`` pages (``models.init_caches``; the draft's only with
        ``with_draft`` and a draft model). Tables hold ``max_len``
        positions. Row b's PRNG key derives from (seed, b). Callers arm
        rows by writing ``gen``/``n``/``m``/``done``/``temp``/``tables``."""
        bs, dtype = self.kv_block_size, resolve_kv_dtype(self.kv_dtype)
        return DecodeState(
            gen=jnp.zeros((batch, gen_len), jnp.int32),
            n=jnp.full((batch,), 2, jnp.int32),
            m=jnp.ones((batch,), jnp.int32),
            done=jnp.ones((batch,), bool),
            tcache=init_caches(self.tc, batch, num_blocks, bs, dtype=dtype),
            dcache=(init_caches(self.dc, batch, num_blocks, bs, dtype=dtype)
                    if with_draft and self.dc is not None else None),
            tables=jnp.zeros((batch, blocks_for(self.max_len, bs)),
                             jnp.int32),
            temp=jnp.zeros((batch,), jnp.float32),
            rngs=acceptance.make_row_keys(seed, np.arange(batch)),
            tree_idx=(jnp.zeros((batch,), jnp.int32)
                      if self.tree is not None else None),
            pf_pos=jnp.zeros((batch,), jnp.int32),
            pf_len=jnp.zeros((batch,), jnp.int32),
            moe_rows=(jnp.zeros((), jnp.int32) if self.counts_moe_rows
                      else None))

    def _uniform_state(self, prompt: Array, gen_len: int, n_tokens: int, *,
                       with_draft: bool, seed: int) -> DecodeState:
        """``init_state`` with every row armed for ``generate_*``: the
        prompt committed in ``gen``, and pages for ``n_tokens`` positions a
        row (``row_tables``)."""
        b, p = prompt.shape
        num_blocks, tables = row_tables(b, n_tokens, self.max_len,
                                        self.kv_block_size)
        state = self.init_state(b, gen_len, num_blocks, seed=seed,
                                with_draft=with_draft)
        return dataclasses.replace(
            state, gen=state.gen.at[:, :p].set(prompt),
            n=jnp.full((b,), p, jnp.int32),
            m=jnp.full((b,), p - 1, jnp.int32), done=jnp.zeros((b,), bool),
            temp=jnp.full((b,), self.temperature, jnp.float32),
            tables=tables)

    def generate_ar(self, prompt: Array, max_new: int, seed: int = 0):
        """Plain autoregressive decoding (the losslessness reference):
        ``[B, P] -> ([B, P + max_new] tokens, SpecStats)``."""
        b, p = prompt.shape
        # the last step writes position p + max_new - 2
        state = self._uniform_state(prompt, p + max_new + 1, p + max_new,
                                    with_draft=False, seed=seed)

        # AR prefill covers the WHOLE prompt: its last logits commit the
        # first new token, so exactly max_new forwards produce max_new
        # tokens (unlike spec prefills, which stop at prompt[:-1] and let
        # the first verify window re-read x_{P-1})
        def pre(params, toks, c, tables, temp, keys):
            logits, c, _ = self._target_forward(
                params, toks, c, jnp.zeros((toks.shape[0],), jnp.int32),
                tables)
            return _pick_next(logits[:, -1], temp, keys), c
        prefill = self._fn("ar_prefill", pre, donate=(2,))
        step = self._fn("ar_step", self._build_ar_step(), donate=(1,))
        params = (self.tp, self.dp)

        next_keys, use = acceptance.split_row_keys(state.rngs)
        first, tcache = prefill(params, prompt, state.tcache, state.tables,
                                state.temp, use)
        state = dataclasses.replace(
            state, gen=state.gen.at[:, p].set(first),
            n=state.n + 1, tcache=tcache, rngs=next_keys)
        for _ in range(max_new - 1):
            state = step(params, state)
        tokens = state.gen[:, :p + max_new]
        stats = SpecStats(max_new, max_new * b, 0, max_new, None, 0.0, 1.0)
        return tokens, stats

    def _pard_depth_logits(self, params, gen, n, m, dcache, tables, pfinfo):
        """ONE PARD draft forward (Eq. 7): proposal logits for every depth
        1..K. Slot A-1 (the last real token) proposes depth 1, the K-1 mask
        slots the rest. Returns (lg [B, K, V], new draft cache, the
        forward's MoE expert rows).

        ``pfinfo = (prefilling, pf, cl)``: prefilling rows consume a
        ``cl``-token prompt chunk at cursor ``pf`` through the SAME forward
        instead of the mask window — their proposal logits are garbage and
        masked out by the caller's commit logic."""
        k, dc = self.k, self.dc
        d_has_ssm = _has_ssm(dc)
        tok = _draft_window(gen, n, m, k, dc.mask_token_id)
        pos = m
        ssm_idx = n - m - 1          # state after the last real token (A-1)
        prefilling, pf, cl = pfinfo
        chunk = _chunk_window(gen, pf, cl, 2 * k)
        tok = jnp.where(prefilling[:, None], chunk, tok)
        pos = jnp.where(prefilling, pf, pos)
        ssm_idx = jnp.where(prefilling, cl - 1, ssm_idx)
        logits, dcache, aux = self._draft_forward(
            params, tok, dcache, pos, tables, collect_ssm=d_has_ssm)
        if d_has_ssm:
            dcache = gather_ssm_states(dc, dcache, ssm_idx)
        a = n - m
        sl = (a - 1)[:, None] + jnp.arange(k)[None, :]
        lg = jax.vmap(lambda row, s: row[s])(logits, sl)   # [B, K, V]
        return lg, dcache, aux["moe_rows"]

    # ------------------------------------------------------------- shared
    def _build_spec_step(self, mode: str, greedy_only: bool = False):
        """One flat speculative step (``generate_spec`` and the serving
        engine's unified step, DESIGN.md §8). It also consumes a
        ``chunk_width``-token prompt chunk for every PREFILLING row
        (``state.pf_pos < state.pf_len``) inside the same draft + verify
        forwards: prefilling rows substitute chunk tokens / cursor
        positions for the draft and verify windows, commit nothing, and
        advance ``pf_pos`` on device — admission never runs a standalone
        prefill forward and decoding rows never stall.

        ``greedy_only=True``: compile-time removal of the sampled branches
        and the per-step PRNG key splitting (see _build_tree_step) — token-
        identical for batches where no live row samples."""
        k = self.k
        tc, dc = self.tc, self.dc
        mask_id = dc.mask_token_id
        t_has_ssm = _has_ssm(tc)
        d_has_ssm = _has_ssm(dc)
        cw = self.chunk_width                           # == k + 1 (flat)

        def propose_pard(params, gen, n, m, dcache, tables, temp, dkeys,
                         pfinfo):
            lg, dcache, rows = self._pard_depth_logits(
                params, gen, n, m, dcache, tables, pfinfo)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if greedy_only:
                return greedy, None, dcache, 1, rows        # 1 draft forward
            scaled = acceptance.scale_logits(lg, temp)      # [B, K, V]

            def samp():
                s = jax.vmap(lambda kk, row: jax.random.categorical(kk, row))(
                    dkeys, scaled).astype(jnp.int32)        # [B, K]
                return jnp.where((temp > 0)[:, None], s, greedy)

            props = jax.lax.cond(jnp.any(temp > 0), samp, lambda: greedy)
            return props, scaled, dcache, 1, rows           # 1 draft forward

        def propose_vsd(params, gen, n, m, dcache, tables, temp, dkeys,
                        pfinfo):
            # call 1: advance committed window, propose token 1
            tok = _draft_window(gen, n, m, k, mask_id)[:, :k + 1]  # reals only
            pos = m
            ssm_idx = n - m - 1
            prefilling, pf, cl = pfinfo
            tok = jnp.where(prefilling[:, None],
                            _chunk_window(gen, pf, cl, k + 1), tok)
            pos = jnp.where(prefilling, pf, pos)
            ssm_idx = jnp.where(prefilling, cl - 1, ssm_idx)
            logits, dcache, aux = self._draft_forward(
                params, tok, dcache, pos, tables, collect_ssm=d_has_ssm)
            rows = aux["moe_rows"]
            a = n - m
            if d_has_ssm:
                # roll SSM state back to "after the last real token"; the AR
                # proposal calls below advance a throwaway copy, the next
                # iteration restarts from this snapshot.
                dcache = gather_ssm_states(dc, dcache, ssm_idx)
            snapshot = dcache
            lg_list = [jax.vmap(lambda row, i: row[i])(logits, a - 1)]
            props = []
            cur_pos = n
            for j in range(k - 1 + 1):
                lgj = lg_list[-1]
                if greedy_only:
                    pj = jnp.argmax(lgj, axis=-1).astype(jnp.int32)
                else:
                    pj = _pick_next(lgj, temp,
                                    acceptance.fold_row_keys(dkeys, j))
                props.append(pj)
                if j == k - 1:
                    break
                lgn, dcache, aux = self._draft_forward(
                    params, pj[:, None], dcache, cur_pos, tables)
                rows = rows + aux["moe_rows"]
                cur_pos = cur_pos + 1
                lg_list.append(lgn[:, 0])
            props = jnp.stack(props, axis=1)                # [B, K]
            if greedy_only:
                return props, None, snapshot, k, rows       # K draft forwards
            scaled = acceptance.scale_logits(
                jnp.stack(lg_list, axis=1), temp)           # [B, K, V]
            return props, scaled, snapshot, k, rows         # K draft forwards

        propose = propose_pard if mode == "pard" else propose_vsd

        def step(params, state: DecodeState):
            gen, n, m, done = state.gen, state.n, state.m, state.done
            tcache, dcache, tables = state.tcache, state.dcache, state.tables
            temp = state.temp
            if greedy_only:
                next_keys = state.rngs          # streams never consumed
                dkeys = akeys = None
            else:
                next_keys, use = acceptance.split_row_keys(state.rngs)
                dkeys = acceptance.fold_row_keys(use, 0)
                akeys = acceptance.fold_row_keys(use, 1)
            prefilling, pf = _phase(state)
            cl = jnp.minimum(cw, state.pf_len - pf)
            pfinfo = (prefilling, pf, cl)
            # a prefilling row does not consume its sampling stream, so a
            # request's sampled trajectory is invariant to HOW its prompt
            # was prefilled (chunk schedule, prefix-cache hits)
            if not greedy_only:
                next_keys = jnp.where(prefilling[:, None], state.rngs,
                                      next_keys)
            with jax.named_scope(spans.DRAFT):
                props, scaled_q, dcache, n_draft, rows = propose(
                    params, gen, n, m, dcache, tables, temp, dkeys, pfinfo)

            with jax.named_scope(spans.TARGET):
                # verify window: [last committed, d_1..d_K]
                last = jnp.take_along_axis(gen, (n - 1)[:, None], axis=1)
                vin = jnp.concatenate([last.astype(jnp.int32), props], axis=1)
                vpos = n - 1
                vin = jnp.where(prefilling[:, None],
                                _chunk_window(gen, pf, cl, k + 1), vin)
                vpos = jnp.where(prefilling, pf, vpos)
                logits, tcache_new, aux = self._target_forward(
                    params, vin, tcache, vpos, tables, collect_ssm=t_has_ssm)
                rows = rows + aux["moe_rows"]

            # acceptance (core/acceptance.py): greedy rule for temp == 0
            # rows, Leviathan sampling for temp > 0 rows — row-selected so
            # one batch mixes both; the sampled branch (softmaxes + accept
            # draws) only executes when some row actually samples
            with jax.named_scope(spans.ACCEPT):
                a_g, acc_g, commit_g = acceptance.greedy_chain_accept(
                    logits, props)
                if greedy_only:
                    a, accepted, commit_tok = a_g, acc_g, commit_g
                else:
                    def samp_accept():
                        qprob = jax.nn.softmax(scaled_q, axis=-1)  # [B,K,V]
                        p_full = acceptance.temp_softmax(logits, temp)
                        return acceptance.leviathan_accept(p_full, qprob,
                                                           props, akeys)

                    a_s, acc_s, commit_s = jax.lax.cond(
                        jnp.any(temp > 0), samp_accept,
                        lambda: (jnp.zeros_like(a_g), jnp.zeros_like(acc_g),
                                 jnp.zeros_like(commit_g)))
                    sampled = temp > 0
                    a = jnp.where(sampled, a_s, a_g)
                    accepted = jnp.where(sampled[:, None], acc_s, acc_g)
                    commit_tok = jnp.where(sampled, commit_s, commit_g)

            with jax.named_scope(spans.COMMIT):
                # frozen rows commit nothing: done rows stay done, prefilling
                # rows consumed a prompt chunk instead of a verify window
                frozen = done | prefilling

                # committed tokens this iteration: d_1..d_a, then commit_tok
                j = jnp.arange(k + 1)[None, :]
                props_ext = jnp.concatenate([props, props[:, -1:]], axis=1)
                vec = jnp.where(j < a[:, None], props_ext,
                                jnp.where(j == a[:, None],
                                          commit_tok[:, None], 0))
                # frozen rows: rewrite what's already there
                old = jax.vmap(lambda g, p: jax.lax.dynamic_slice(
                    g, (p,), (k + 1,)))(gen, n)
                vec = jnp.where(frozen[:, None], old, vec)
                gen = _row_write(gen, vec.astype(gen.dtype), n)

                n_commit = jnp.where(frozen, 0, a + 1)
                new_m = jnp.where(frozen, m, n)
                new_n = n + n_commit

                if t_has_ssm:
                    # state after input index a (last committed token
                    # processed); prefilling rows keep the state after their
                    # chunk's last REAL token (pads excluded — DESIGN.md §3
                    # unchanged)
                    ssm_idx = jnp.where(prefilling, cl - 1, a)
                    tcache_new = gather_ssm_states(tc, tcache_new, ssm_idx)
                # frozen rows keep old caches? their cache contents are
                # untouched at positions < n and never read beyond; safe to
                # keep new buffers.
                acc_hist = jnp.sum(
                    jnp.where(frozen[:, None], 0, accepted), axis=0)  # [K]
                # chain = one sibling per depth: round 0 holds every accept
                round_hist = jnp.sum(
                    jnp.where(frozen, 0, a))[None].astype(jnp.int32)
                # per-row accepted rank (chain: rank 0 everywhere it
                # accepted; -1 rejected/frozen) — the adaptive tree
                # controller's signal, shaped like the tree step's so callers
                # share one unpacking
                rank = jnp.where(
                    (jnp.arange(1, k + 1)[None, :] <= a[:, None])
                    & ~frozen[:, None], 0, -1).astype(jnp.int32)
                new_state = dataclasses.replace(
                    state, gen=gen, n=new_n, m=new_m, tcache=tcache_new,
                    dcache=dcache, rngs=next_keys,
                    pf_pos=jnp.where(prefilling, pf + cl, state.pf_pos),
                    moe_rows=None if state.moe_rows is None else rows)
                return (new_state, jnp.where(frozen, 0, a), acc_hist,
                        round_hist, rank, n_draft)

        return step

    # --------------------------------------------------------------- tree
    def _build_tree_step(self, greedy_only: bool = False):
        """One tree-verification step over PER-ROW templates (DESIGN.md
        §6/§7).

        ``greedy_only=True`` compiles a variant with the sampled machinery
        removed at trace time — no ``lax.cond`` fusion barriers, no per-step
        threefry key splitting (the per-row serial while-loops XLA:CPU
        lowers them to). Callers select it when no live row samples (host
        knowledge at dispatch time); tokens are identical either way because
        greedy output never reads the PRNG streams, and a sampled row's key
        is freshly (seed, rid)-derived at admission.

        Each row's packed tree metadata (ancestor bitmasks, parent/depth/
        choice arrays, child map, slot count) is gathered from the static
        ``TemplateBank`` by ``state.tree_idx``, so one jitted step serves a
        batch mixing tree shapes — a bank of one reproduces the old static
        behaviour exactly. Draft: ONE PARD forward (the flat mask window)
        yields one proposal distribution per depth. Greedy rows
        (state.temp == 0) populate their template with the top-b_d tokens
        per depth; sampled rows draw every node i.i.d. from its depth's
        softmax(logits / temp). Verify: ONE target forward over the packed
        tree with ancestor-mask attention, logical positions root+depth;
        per-row window lengths (``TreeAttnInfo.win_len``) bound each row's
        KV sweep to its own template. Commit (core/acceptance.py,
        row-selected): greedy rows keep the longest root path matching the
        target argmax — exactly the AR greedy sequence — while sampled rows
        run multi-round recursive rejection sampling over each surviving
        node's children, committing tokens distributed exactly as the
        target model's own sampling distribution. Only the winning path's
        KV survives: compact_tree_caches moves it onto the committed
        positions; losing branches (and slots past a row's template) are
        re-covered by the next window's cache_pos like flat-K rejects.

        Prefilling rows ride the same two forwards with prompt chunks
        (DESIGN.md §8). In the packed tree window a chunk is
        just a CAUSAL "tree": ancestor bitmask = all-lower-bits, win_len =
        the chunk's real token count, positions = cursor + slot — so the
        tree kernels serve mixed prefill/decode batches unchanged.
        """
        bank = self.tree
        tc, dc = self.tc, self.dc
        assert bank is not None
        d, s = bank.max_depth, bank.max_slots
        max_b = bank.max_branching
        cw = self.chunk_width                       # min(2K, max_slots)
        # causal ancestor-or-self bitmask: window slot i sees slots 0..i
        chain_anc = (~jnp.uint32(0)) >> jnp.uint32(31 - jnp.arange(s))
        bank_parent = jnp.asarray(bank.parent)                     # [T, S]
        bank_depth = jnp.asarray(bank.depth)
        bank_choice = jnp.asarray(bank.choice)
        bank_anc = jnp.asarray(bank.anc)                           # [T, S]
        bank_cmap = jnp.asarray(bank.child_map)                    # [T,S,MB]
        bank_nslots = jnp.asarray(bank.nslots)                     # [T]

        def step(params, state: DecodeState):
            gen, n, m, done = state.gen, state.n, state.m, state.done
            tcache, dcache, tables = state.tcache, state.dcache, state.tables
            temp = state.temp
            if greedy_only:
                next_keys = state.rngs          # streams never consumed
                dkeys = akeys = None
            else:
                next_keys, use = acceptance.split_row_keys(state.rngs)
                dkeys = acceptance.fold_row_keys(use, 0)
                akeys = acceptance.fold_row_keys(use, 1)

            # per-row template metadata, gathered from the static bank
            sel = state.tree_idx
            parent, depth = bank_parent[sel], bank_depth[sel]      # [B, S]
            choice, anc = bank_choice[sel], bank_anc[sel]
            cmap, nslots = bank_cmap[sel], bank_nslots[sel]
            node_depth = depth[:, 1:]                              # [B, N]

            prefilling, pf = _phase(state)
            cl = jnp.minimum(cw, state.pf_len - pf)
            pfinfo = (prefilling, pf, cl)
            # prefilling rows keep their sampling stream untouched (see
            # _build_spec_step): sampled output is prefill-schedule- and
            # prefix-cache-invariant
            if not greedy_only:
                next_keys = jnp.where(prefilling[:, None], state.rngs,
                                      next_keys)

            with jax.named_scope(spans.DRAFT):
                # draft: depth distributions -> per-row template tokens. One
                # top-max_b per depth covers every template's ranks;
                # _topk_indices and argmax share lowest-index tie-breaking, so
                # rank 0 IS the flat path's argmax (degenerate-chain identity).
                lg, dcache, rows = self._pard_depth_logits(
                    params, gen, n, m, dcache, tables, pfinfo)
                topk = _topk_indices(lg, max_b)                    # [B,D,MB]
                di = jnp.maximum(node_depth - 1, 0)
                per_node = jnp.take_along_axis(
                    topk, di[:, :, None], axis=1)                  # [B,N,MB]
                props_g = jnp.take_along_axis(
                    per_node, choice[:, 1:, None], axis=2)[..., 0]  # [B, N]
                if greedy_only:
                    props = props_g
                else:
                    # sampled rows: i.i.d. candidates per node (multi-round
                    # acceptance requires sibling draws from q, not top-k);
                    # the per-node draws only execute when some row actually
                    # samples
                    scaled = acceptance.scale_logits(lg, temp)     # [B,D,V]
                    any_sampled = jnp.any(temp > 0)
                    props_s = jax.lax.cond(
                        any_sampled,
                        lambda: acceptance.sample_tree_props_rows(
                            scaled, node_depth, dkeys),
                        lambda: props_g)
                    sampled = temp > 0
                    props = jnp.where(sampled[:, None], props_s, props_g)

            with jax.named_scope(spans.TARGET):
                # verify: one target forward over the packed tree; per-row
                # win_len bounds each row's window to its own template
                last = jnp.take_along_axis(gen, (n - 1)[:, None], axis=1)
                vin = jnp.concatenate([last.astype(jnp.int32), props], axis=1)
                positions = (n - 1)[:, None] + depth
                win_start, win_anc, win_len = n - 1, anc, nslots
                # prefilling rows: a cl-token causal chunk through the same
                # packed window (pads past cl are invisible and re-covered).
                # Slice at the chunk width cw — guaranteed inside the gen
                # buffer by the slack validation — and pad to the window:
                # slicing at s (up to 32) could clamp near max_len and
                # silently shift the chunk.
                chunk = _chunk_window(gen, pf, cl, cw)
                chunk = jnp.pad(chunk, ((0, 0), (0, s - cw)))
                vin = jnp.where(prefilling[:, None], chunk, vin)
                positions = jnp.where(
                    prefilling[:, None],
                    pf[:, None] + jnp.arange(s)[None, :], positions)
                win_start = jnp.where(prefilling, pf, win_start)
                win_anc = jnp.where(prefilling[:, None],
                                    chain_anc[None, :], win_anc)
                win_len = jnp.where(prefilling, cl, win_len)
                tinfo = TreeAttnInfo(win_start=win_start, anc=win_anc,
                                     win_len=win_len)
                logits, tcache_new, aux = self._target_forward(
                    params, vin, tcache, win_start, tables,
                    positions=positions, tree_info=tinfo)
                rows = rows + aux["moe_rows"]

            with jax.named_scope(spans.ACCEPT):
                # acceptance (core/acceptance.py), row-selected greedy/sampled;
                # the multi-round machinery only executes when a row samples
                a_g, tok_g, slot_g, commit_g, rank_g = \
                    acceptance.greedy_tree_accept_rows(
                        logits, props, parent, depth, choice, anc, nslots, d)

                if greedy_only:
                    a, tok_depth, src_slot = a_g, tok_g, slot_g
                    commit_tok, rank = commit_g, rank_g
                else:
                    def samp_accept():
                        # [B, S, V] and [B, D, V]
                        p_full = acceptance.temp_softmax(logits, temp)
                        q_depth = jax.nn.softmax(scaled, axis=-1)
                        return acceptance.sampled_tree_accept_rows(
                            p_full, q_depth, props, cmap, akeys, d, max_b)

                    a_s, tok_s, slot_s, commit_s, rank_s = jax.lax.cond(
                        any_sampled, samp_accept,
                        lambda: (jnp.zeros_like(a_g), jnp.zeros_like(tok_g),
                                 jnp.zeros_like(slot_g),
                                 jnp.zeros_like(commit_g),
                                 jnp.full_like(rank_g, -1)))
                    a = jnp.where(sampled, a_s, a_g)
                    tok_depth = jnp.where(sampled[:, None], tok_s, tok_g)
                    src_slot = jnp.where(sampled[:, None], slot_s, slot_g)
                    commit_tok = jnp.where(sampled, commit_s, commit_g)
                    rank = jnp.where(sampled[:, None], rank_s,
                                     rank_g)                       # [B, D]

            with jax.named_scope(spans.COMMIT):
                # frozen rows commit nothing: done rows stay done, prefilling
                # rows consumed a prompt chunk instead of a verify window
                frozen = done | prefilling

                dflt = jnp.arange(1, d + 1, dtype=jnp.int32)[None, :]
                # rejected depths and frozen rows: identity copy (src == dst)
                src_slot = jnp.where((src_slot > 0) & ~frozen[:, None],
                                     src_slot, dflt)

                # committed tokens this iteration: path d_1..d_a, then
                # commit_tok
                j = jnp.arange(d + 1)[None, :]
                tok_ext = jnp.concatenate([tok_depth, tok_depth[:, -1:]],
                                          axis=1)
                vec = jnp.where(j < a[:, None], tok_ext,
                                jnp.where(j == a[:, None],
                                          commit_tok[:, None], 0))
                old = jax.vmap(lambda g, p: jax.lax.dynamic_slice(
                    g, (p,), (d + 1,)))(gen, n)
                vec = jnp.where(frozen[:, None], old, vec)
                gen = _row_write(gen, vec.astype(gen.dtype), n)

            with jax.named_scope(spans.TREE_COMPACT):
                # only the winning path's KV survives at committed positions
                src_pos = (n - 1)[:, None] + src_slot                  # [B, D]
                tcache_new = compact_tree_caches(
                    tc, tcache_new, src_pos, n, d, tables, self.kv_block_size)

            with jax.named_scope(spans.COMMIT):
                n_commit = jnp.where(frozen, 0, a + 1)
                new_m = jnp.where(frozen, m, n)
                new_n = n + n_commit
                hist = jnp.sum(
                    jnp.where(frozen[:, None], 0,
                              (a[:, None] > jnp.arange(d)[None, :])
                              .astype(jnp.int32)), axis=0)             # [D]
                # per-round accept counts: which sibling rank won at each
                # accepted depth (rank == -1 where the depth rejected)
                valid = (rank >= 0) & ~frozen[:, None]                 # [B, D]
                round_hist = jnp.sum(
                    (rank[:, :, None] == jnp.arange(max_b)[None, None, :])
                    & valid[:, :, None], axis=(0, 1)).astype(jnp.int32)
                rank = jnp.where(frozen[:, None], -1, rank)
                new_state = dataclasses.replace(
                    state, gen=gen, n=new_n, m=new_m, tcache=tcache_new,
                    dcache=dcache, rngs=next_keys,
                    pf_pos=jnp.where(prefilling, pf + cl, state.pf_pos),
                    moe_rows=None if state.moe_rows is None else rows)
                return new_state, jnp.where(frozen, 0, a), hist, round_hist, \
                    rank, 1

        return step

    def generate_spec(self, prompt: Array, max_new: int, mode: str = "pard",
                      seed: int = 0, tree_idx=None):
        """``tree_idx`` ([B] ints) pins each row to a bank template for the
        whole run (tree drafting only; default: template 0 — with a
        single-template bank, exactly the old static behaviour)."""
        assert self.dp is not None, "spec decoding requires a draft model"
        if self.tree is not None:
            assert mode == "pard", "tree templates require mode='pard'"
        else:
            assert tree_idx is None, "tree_idx requires a TemplateBank"
        b, p = prompt.shape
        k = self.k
        # Both prefills stop at prompt[:-1]: the verify window re-processes
        # x_{P-1} (an idempotent KV rewrite for attention — but SSM state
        # must NOT see it twice, so it is excluded here).
        assert p >= 2, "prompts must have at least 2 tokens"
        L = p + max_new + self.window_slack   # room for the final window
        state = self._uniform_state(prompt, L, L, with_draft=True, seed=seed)
        if tree_idx is not None:
            idx = np.asarray(tree_idx, np.int32)
            assert idx.shape == (b,) and idx.min() >= 0 \
                and idx.max() < len(self.tree), idx
            state = dataclasses.replace(state, tree_idx=jnp.asarray(idx))

        bs = self.kv_block_size
        prefill_t = self._fn("sp_prefill_t", lambda p, t, c, tb: prefill_row(
            p, self.tc, t, c, tables=tb, block_size=bs,
            enc_out=self.enc_out), donate=(2,))
        prefill_d = self._fn("sp_prefill_d", lambda p, t, c, tb: prefill_row(
            p, self.dc, t, c, tables=tb, block_size=bs,
            enc_out=self.draft_enc_out), donate=(2,))
        # donate the whole state: the steady state then updates gen + both
        # cache pools in place (no per-iteration multi-MB buffer copies)
        # greedy batches compile the sampled machinery out entirely (no
        # per-step threefry splits, no lax.cond fusion barriers)
        go = self.temperature == 0.0
        sfx = "_g" if go else ""
        if self.tree is not None:
            step = self._fn(f"tree_step_{self.tree.key}{sfx}",
                            self._build_tree_step(greedy_only=go),
                            donate=(1,))
        else:
            step = self._fn(f"spec_step_{mode}{sfx}",
                            self._build_spec_step(mode, greedy_only=go),
                            donate=(1,))

        state = dataclasses.replace(
            state, tcache=prefill_t(self.tp, prompt[:, :-1], state.tcache,
                                    state.tables),
            dcache=prefill_d(self.dp, prompt[:, :-1], state.dcache,
                             state.tables))

        params = (self.tp, self.dp)
        iters, draft_calls, target_calls = 0, 0, 0
        acc_hist = jnp.zeros((k,), jnp.int32)
        round_hist = None
        acc_total, live_iters = 0, 0
        target_n = p + max_new
        while True:
            live = int(jnp.sum(~state.done))
            state, a, hist, rhist, _rank, n_draft = step(params, state)
            iters += 1
            live_iters += live
            draft_calls += n_draft
            target_calls += 1
            acc_hist = acc_hist + hist
            round_hist = rhist if round_hist is None else round_hist + rhist
            acc_total += int(jnp.sum(a))
            state = dataclasses.replace(state, done=state.n >= target_n)
            if bool(jnp.all(state.done)) or iters > max_new + 2:
                break

        n, gen = state.n, state.gen
        tokens = gen[:, :target_n]
        live_iters = max(live_iters, 1)
        stats = SpecStats(
            iterations=iters,
            tokens_generated=int(jnp.sum(jnp.minimum(n, target_n) - p)),
            draft_forwards=draft_calls,
            target_forwards=target_calls,
            accept_hist=jax.device_get(acc_hist),
            acceptance_rate=acc_total / (live_iters * k),
            mean_accepted=acc_total / live_iters + 1.0,
            round_hist=jax.device_get(round_hist),
        )
        return tokens, stats
