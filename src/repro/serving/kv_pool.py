"""Block-paged KV-cache pool — the one KV layout (DESIGN.md §5), vLLM-style.

HBM footprint follows what requests actually use, not ``max_batch x
max_len``:

  * attention KV lives in a shared pool of fixed-size blocks
    ``[num_blocks, block_size, ...]`` (per layer; scanned layers carry a
    leading repeats dim);
  * each slot owns a *block table* row ``[max_blocks_per_seq]`` mapping
    absolute position ``p`` to ``(table[p // block_size], p % block_size)``;
  * a host-side free list hands blocks out at admission and takes them back
    in O(1) at completion. Prefill writes straight into the allocated blocks
    through the table (copy-free admission — no full-pool row scatter);
  * SSM / conv states are O(1) per row and stay batch-indexed.

Invariants (tested in tests/test_engine.py, tests/test_kv_pool.py and
tests/test_prefix_cache.py):

  I1. Block 0 is RESERVED as the garbage block. Unallocated table entries
      are 0, so any write past a row's allocation lands there; reads never
      see it because validity is ``kv_index < kv_len``.
  I2. Every block a slot can WRITE is owned by exactly one slot
      (refcount 1), so the flattened scatter in
      models.attention.write_cache_paged never collides. Prefix-cached
      blocks map into several tables at once (refcount = #mappers) but are
      READ-ONLY: every position a row writes lies past its shared prefix
      (DESIGN.md §8), and ``copy_on_write`` exists as the escape hatch.
  I3. A slot's allocation covers every position the decode loop can write:
      ``prompt + max_new + 2K + 2`` tokens (the speculative write window).
  I4. A released slot's table row is zeroed (on host) before its blocks can
      be handed to another slot, so a frozen row's stale writes route to
      the garbage block, never into a new owner's blocks.
  I5. A block is on the free list or the eviction LRU iff its refcount is
      zero; matching only returns COMPUTED blocks (content fully written by
      the registering row's prefill), so a cache hit can never serve
      half-prefilled KV.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import blocks_for, init_caches
from ..models.config import (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, ModelConfig,
                             scan_plan)

ATTN_MIXERS = (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA)


def default_num_blocks(max_batch: int, max_len: int, block_size: int) -> int:
    """Worst-case pool size (every slot filled to max_len) + garbage block.

    Serving deployments pass something smaller and rely on admission
    backpressure; this default never refuses an admission max_len allows.
    """
    return max_batch * blocks_for(max_len, block_size) + 1


# ---------------------------------------------------------------------------
# Pool init
# ---------------------------------------------------------------------------

def init_paged_caches(cfg: ModelConfig, batch: int, num_blocks: int,
                      block_size: int, dtype=jnp.bfloat16, mesh=None):
    """``models.init_caches``: attention leaves are shared block pools
    [NB, bs, ...] (no batch dim); SSM states are [batch, ...]. ``dtype``
    accepts a kv_dtype name ("bf16"/"fp32"/"int8"/"fp8") or a jnp dtype;
    quantized dtypes add sibling *_scale pool leaves. ``mesh`` shards the
    pools for tensor-parallel serving per sharding.specs.paged_cache_specs
    (KV heads over the "model" axis, quant scales alongside, everything
    else replicated — DESIGN.md §11)."""
    pool = init_caches(cfg, batch, num_blocks, block_size, dtype=dtype)
    if mesh is not None:
        from ..sharding import specs as _specs
        pool = jax.device_put(
            pool, _specs.to_named(_specs.paged_cache_specs(pool, mesh), mesh))
    return pool


# ---------------------------------------------------------------------------
# Bytes accounting
# ---------------------------------------------------------------------------

def _attn_leaves(cfg: ModelConfig, tree):
    plan = scan_plan(cfg)
    out = []
    for i, s in enumerate(plan.prefix):
        if s.mixer in ATTN_MIXERS:
            out += jax.tree.leaves(tree["prefix"][i])
    for j, s in enumerate(plan.period):
        if s.mixer in ATTN_MIXERS:
            out += jax.tree.leaves(tree["scan"][j])
    return out


def kv_capacity_bytes(cfg: ModelConfig, tree) -> int:
    """HBM resident for the attention KV pools."""
    return int(sum(leaf.nbytes for leaf in _attn_leaves(cfg, tree)))


def kernel_pools(cfg: ModelConfig, tree):
    """The first attention layer's pool leaves as a paged attention kernel
    is handed them, ``[NB, bs, ...]`` shapes and dtypes (a scanned leaf
    without its repeats axis); empty without an attention layer."""
    plan = scan_plan(cfg)
    for entries, specs, lead in ((tree["prefix"], plan.prefix, 0),
                                 (tree["scan"], plan.period, 1)):
        for entry, spec in zip(entries, specs):
            if spec.mixer in ATTN_MIXERS:
                return [jax.ShapeDtypeStruct(x.shape[lead:], x.dtype)
                        for x in jax.tree.leaves(entry)]
    return []


def kv_bytes_per_block(cfg: ModelConfig, tree, num_blocks: int) -> int:
    """Bytes one pool block costs across all attention leaves (scanned
    leaves count each repeat, since the pool exists per repeat-layer)."""
    return int(sum(leaf.nbytes // num_blocks
                   for leaf in _attn_leaves(cfg, tree)))


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def prefix_block_keys(prompt, block_size: int,
                      kv_dtype: str = "bf16") -> List[bytes]:
    """Content-chained cache keys for the FULL blocks inside ``prompt[:-1]``
    (the region admission prefills — the last prompt token is re-processed
    by the first verify window and its block is written by decode).

    ``key[i]`` identifies the exact token prefix ``prompt[:(i+1)*bs]``: the
    raw byte string of the prefix, so two prompts share a key iff they share
    the tokens verbatim — content-exact, no hash collisions, and chaining is
    implicit (a block's key embeds every preceding token). Target and draft
    KV are keyed TOGETHER: both models cache the same absolute positions
    through one shared block table, so one key covers both pools.

    Keys are SALTED with ``kv_dtype``: a block's cached payload is the
    dtype-specific encoding (quantized values + scales vs full precision),
    so the same token prefix under different kv_dtypes must never alias —
    an int8 engine re-reading an fp32 engine's key (or vice versa) would
    serve bytes in the wrong encoding.
    """
    p = np.ascontiguousarray(np.asarray(prompt, np.int32))
    n_full = max(0, (len(p) - 1)) // block_size
    salt = kv_dtype.encode() + b"|"
    return [salt + p[:(i + 1) * block_size].tobytes() for i in range(n_full)]


class PrefixIndex:
    """The one shared content-keyed prefix-cache index spanning every
    engine replica (DESIGN.md §12).

    Block ids are physical pool slots and mean nothing across replicas, so
    each replica's :class:`BlockAllocator` keeps its own ``key -> block``
    map; this object is the registry of those per-replica maps. Admission
    asks :meth:`best_replica` which replica already holds a prompt's
    leading blocks (prefix-affinity routing) — a hit routes the request to
    the owning replica, a miss falls back to least-loaded. With ``dp=1``
    the index degenerates to a thin wrapper over the single allocator and
    routing is a no-op.
    """

    def __init__(self):
        self.allocators: Dict[int, "BlockAllocator"] = {}

    def register(self, replica: int, alloc: "BlockAllocator") -> None:
        """Attach ``alloc`` as replica ``replica``'s block map (done by
        ``BlockAllocator.__init__`` when constructed with this index)."""
        if replica in self.allocators:
            raise ValueError(f"replica {replica} already registered")
        self.allocators[replica] = alloc

    def match(self, keys: Sequence[bytes]) -> Dict[int, List[int]]:
        """Per-replica ``match_prefix`` results for ``keys`` (pure query)."""
        return {r: a.match_prefix(keys)
                for r, a in sorted(self.allocators.items())}

    def best_replica(self, keys: Sequence[bytes]):
        """``(replica, blocks)`` for the replica holding the LONGEST
        computed cached prefix of ``keys``, or ``(None, [])`` when no
        replica holds any block. Ties go to the lowest replica id (stable
        under re-query, so routing is deterministic)."""
        best_r, best = None, []
        for r, a in sorted(self.allocators.items()):
            m = a.match_prefix(keys)
            if len(m) > len(best):
                best_r, best = r, m
        return best_r, best


class BlockAllocator:
    """Host-side refcounted block allocator + block-table shadow + prompt
    prefix cache (DESIGN.md §5/§8).

    The device copy of ``tables`` is refreshed by the engine whenever
    ``version`` changes (admission / release / COW), so frozen rows' stale
    writes always route through an up-to-date table (invariant I4).

    Prefix caching: ``allocate(..., keys=)`` registers the row's full
    prompt blocks under content-exact keys (``prefix_block_keys``); the
    scheduler marks them COMPUTED as the chunked-prefill cursor passes
    them. ``match_prefix`` returns the longest run of computed cached
    blocks for a new prompt; ``allocate(..., prefix=)`` maps them
    copy-free into the new row's table (refcount + 1) so the row only
    prefills the uncovered tail. Released cached blocks (refcount 0) park
    on an LRU instead of the free list and are evicted — unregistered and
    recycled — only when allocation outgrows the free list.
    """

    def __init__(self, num_blocks: int, block_size: int, max_batch: int,
                 max_len: int, *, replica: int = 0,
                 prefix_index: Optional[PrefixIndex] = None):
        assert num_blocks >= 2, "need at least one block beyond the reserved 0"
        self.num_blocks = num_blocks
        # data-parallel serving (DESIGN.md §12): which engine replica this
        # pool backs, and the shared cross-replica index it reports to
        self.replica = replica
        self.prefix_index = prefix_index
        if prefix_index is not None:
            prefix_index.register(replica, self)
        self.block_size = block_size
        self.max_blocks_per_seq = blocks_for(max_len, block_size)
        # LIFO free list; block 0 reserved as the garbage block (I1)
        self.free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.tables = np.zeros((max_batch, self.max_blocks_per_seq), np.int32)
        self.owned: Dict[int, List[int]] = {}
        self.ref = np.zeros(num_blocks, np.int32)     # mappers per block
        self.index: Dict[bytes, int] = {}             # cache key -> block
        self.block_key: Dict[int, bytes] = {}         # block -> cache key
        self.computed: set = set()                    # content fully written
        self.lru: "OrderedDict[int, None]" = OrderedDict()  # ref-0 cached
        # slot -> table indices mapped READ-ONLY (prefix-matched blocks);
        # copy_on_write removes an index once privately remapped
        self.read_only: Dict[int, set] = {}
        self.version = 0

    # -- queries ---------------------------------------------------------
    def blocks_needed(self, n_tokens: int) -> int:
        return blocks_for(n_tokens, self.block_size)

    def can_allocate(self, n_blocks: int,
                     prefix: Sequence[int] = ()) -> bool:
        """True when ``n_blocks`` FRESH blocks are claimable (free list
        plus evictable ref-0 cached blocks). When the admission also maps
        ``prefix`` blocks, pass them: matched blocks currently parked on
        the LRU are about to be ref-bumped OFF it by ``allocate``, so they
        must not be counted as reclaimable too."""
        lru_hits = sum(1 for b in prefix if b in self.lru)
        return len(self.free) + len(self.lru) - lru_hits >= n_blocks

    @property
    def blocks_in_use(self) -> int:
        """Unique blocks mapped by live slots (shared blocks count once —
        that is the point of prefix sharing)."""
        return int((self.ref > 0).sum())

    def match_prefix(self, keys: Sequence[bytes]) -> List[int]:
        """Longest run of cached, COMPUTED blocks covering ``keys`` from
        the front (I5: a half-prefilled registration never matches). Pure
        query: refcounts move in ``allocate(prefix=...)``."""
        out: List[int] = []
        for key in keys:
            b = self.index.get(key)
            if b is None or b not in self.computed:
                break
            out.append(b)
        return out

    # -- internals -------------------------------------------------------
    def _unregister(self, block: int) -> None:
        key = self.block_key.pop(block, None)
        if key is not None and self.index.get(key) == block:
            del self.index[key]
        self.computed.discard(block)

    def _take_block(self) -> int:
        """A writable block: free list first, then evict the LRU ref-0
        cached block (unregistered before reuse, so a stale key can never
        resolve to recycled content)."""
        if self.free:
            return self.free.pop()
        block, _ = self.lru.popitem(last=False)       # least recently parked
        self._unregister(block)
        return block

    # -- mutation --------------------------------------------------------
    def allocate(self, slot: int, n_tokens: int,
                 prefix: Sequence[int] = (),
                 keys: Sequence[bytes] = ()) -> None:
        """Claim blocks covering ``n_tokens`` for ``slot``.

        ``prefix``: cached blocks from ``match_prefix`` to map copy-free as
        the row's leading blocks (refcount + 1; read-only for this row —
        its first writable position lies past them). ``keys``: the row's
        ``prefix_block_keys``; its full prompt blocks are registered under
        them for future reuse (first registration wins when identical
        prompts race)."""
        assert slot not in self.owned, f"slot {slot} already allocated"
        nb = self.blocks_needed(n_tokens)
        if nb > self.max_blocks_per_seq:
            # never clamp: a short allocation would break I3 and let decode
            # attend garbage-block KV as if it were valid context
            raise ValueError(
                f"{n_tokens} tokens need {nb} blocks but a sequence's block "
                f"table holds {self.max_blocks_per_seq} (max_len too small)")
        prefix = list(prefix)
        assert len(prefix) <= nb, "prefix longer than the allocation"
        assert self.can_allocate(nb - len(prefix), prefix), \
            "allocate() without can_allocate()"
        # bump shared refs FIRST so eviction below can never take them
        for b in prefix:
            if self.ref[b] == 0:
                self.lru.pop(b)
            self.ref[b] += 1
        fresh = [self._take_block() for _ in range(nb - len(prefix))]
        for b in fresh:
            self.ref[b] = 1
        blocks = prefix + fresh
        self.owned[slot] = blocks
        self.read_only[slot] = set(range(len(prefix)))
        for i, key in enumerate(keys[:nb]):
            b = blocks[i]
            if key not in self.index and b not in self.block_key:
                self.index[key] = b
                self.block_key[b] = key
        self.tables[slot, :] = 0
        self.tables[slot, :nb] = blocks
        self.version += 1

    def mark_computed(self, slot: int, n_tokens: int) -> None:
        """Flag the slot's registered blocks whose content is fully covered
        by ``n_tokens`` valid cache positions (the prefill cursor) as
        matchable (I5). Called by the scheduler as chunked prefill
        advances; prefix-matched blocks are already computed."""
        for i, b in enumerate(self.owned.get(slot, ())):
            if (i + 1) * self.block_size > n_tokens:
                break
            if b in self.block_key:
                self.computed.add(b)

    def grow(self, slot: int, n_tokens: int) -> bool:
        """Extend a live slot's allocation in place to cover ``n_tokens``
        (adaptive tree reshaping: a request switching to a wider template
        needs a larger write window, I3). Appends blocks to the slot's
        table row; returns False — leaving the allocation untouched — when
        the free list or the table row cannot cover the request, so the
        caller can keep the old template instead. Never shrinks: a narrower
        template simply stops reading the extra blocks (they free with the
        slot, keeping release O(1))."""
        cur = self.owned.get(slot)
        assert cur is not None, f"grow() on unallocated slot {slot}"
        nb = self.blocks_needed(n_tokens)
        if nb <= len(cur):
            return True
        extra = nb - len(cur)
        if nb > self.max_blocks_per_seq or not self.can_allocate(extra):
            return False
        blocks = [self._take_block() for _ in range(extra)]
        for b in blocks:
            self.ref[b] = 1
        self.tables[slot, len(cur):nb] = blocks
        cur.extend(blocks)
        self.version += 1
        return True

    def copy_on_write(self, slot: int,
                      block_idx: int) -> Optional[Tuple[int, int]]:
        """Make the slot's ``block_idx``-th block privately writable.

        Returns ``(old, new)`` when a fresh block was mapped — the CALLER
        must copy the device KV ``old -> new`` in every pool before the
        next forward — or None when the block was already exclusive (a
        sole-owner cached block is detached from the index instead of
        copied: its content is about to diverge from its key)."""
        blocks = self.owned[slot]
        old = blocks[block_idx]
        if self.ref[old] == 1:
            if old in self.block_key:
                self._unregister(old)
            self.read_only.get(slot, set()).discard(block_idx)
            return None
        if not self.can_allocate(1):
            # the caller NEEDS the write — failing to copy would corrupt a
            # shared block — so this is a hard error with a clear message,
            # not the bare KeyError an empty LRU pop would raise. (Callers
            # that can wait should check can_allocate(1) first.)
            raise RuntimeError(
                f"copy-on-write of shared block {old} needs a free block "
                f"but the pool is exhausted; raise kv_num_blocks")
        new = self._take_block()
        self.ref[new] = 1
        self.ref[old] -= 1
        blocks[block_idx] = new
        self.read_only.get(slot, set()).discard(block_idx)
        self.tables[slot, block_idx] = new
        self.version += 1
        return old, new

    def release(self, slot: int) -> List[int]:
        """O(1) in tokens: drop the slot's mappings and zero its table row
        (stale writes -> garbage block, I4). Blocks reaching refcount 0
        return to the free list — except computed cached blocks, which park
        on the eviction LRU so a later identical prompt can still hit them
        (I5); a block with surviving mappers stays exactly where it is."""
        blocks = self.owned.pop(slot, [])
        self.read_only.pop(slot, None)
        for b in blocks:
            self.ref[b] -= 1
            assert self.ref[b] >= 0, f"refcount underflow on block {b}"
            if self.ref[b] == 0:
                if b in self.block_key and b in self.computed:
                    self.lru[b] = None
                    self.lru.move_to_end(b)       # most recently released
                else:
                    self._unregister(b)
                    self.free.append(b)
        self.tables[slot, :] = 0
        self.version += 1
        return blocks
