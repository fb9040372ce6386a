"""Logical-axis sharding rules (MaxText-style) with divisibility fallbacks.

Params are plain nested dicts; each leaf's sharding is chosen by its *leaf
name* via ``PARAM_RULES``: an ordered list of candidate trailing-axis specs.
The first candidate whose named axes all divide the corresponding dims is
used; leading dims (e.g. the scan-stack repeats axis) are padded with None.

Training uses ``fsdp=True``: any dim left unsharded by the tensor rule is
additionally sharded over the data axis when divisible (ZeRO-3 — required
for the big assigned models to have any chance of fitting v5e HBM; see
EXPERIMENTS.md §Dry-run for the honest accounting).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# trailing-dims candidates per leaf name; names resolve via AXIS_MAP
PARAM_RULES: Dict[str, List[Tuple[Optional[str], ...]]] = {
    # embeddings
    "embedding": [("vocab", None)],
    "unembed": [("vocab", None)],
    # gqa attention
    "wq": [(None, "tp", None), ("tp", None, None)],
    "wk": [(None, "tp", None), ("tp", None, None)],
    "wv": [(None, "tp", None), ("tp", None, None)],
    "wo": [("tp", None, None), (None, None, "tp")],
    "bq": [(None, None)], "bk": [(None, None)], "bv": [(None, None)],
    "q_norm": [(None,)], "k_norm": [(None,)],
    # mla
    "w_dq": [(None, "tp")],
    "w_uq": [(None, "tp", None), ("tp", None, None)],
    "w_dkv": [(None, None)],
    "w_uk": [(None, "tp", None), ("tp", None, None)],
    "w_uv": [(None, "tp", None), ("tp", None, None)],
    "q_lora_norm": [(None,)], "kv_lora_norm": [(None,)],
    # mlp
    "wi": [(None, "tp")], "wg": [(None, "tp")],
    # moe — baseline is tensor-parallel WITHIN each expert (experts
    # replicated over model). Expert-parallel sharding (experts dim on
    # model, all-to-all dispatch) compiles pathologically slowly through
    # GSPMD for the grouped one-hot dispatch and is explored as a §Perf
    # experiment via ``expert_parallel_rules`` below, not as the default.
    "we_i": [(None, None, "tp"), ("tp", None, None)],
    "we_g": [(None, None, "tp"), ("tp", None, None)],
    "we_o": [(None, "tp", None), ("tp", None, None)],
    "router": [(None, None)],
    # ssm (mamba2)
    "in_proj": [(None, "tp"), ("tp", None)],
    "conv_w": [(None, None)], "conv_b": [(None,)],
    "A_log": [(None,)], "D": [(None,)], "dt_bias": [(None,)],
    "ssm_norm": [(None,)],
    "out_proj": [("tp", None)],
    # norms
    "scale": [(None,)], "bias": [(None,)],
    # gate scalar (vision cross-attn)
    "gate": [()],
}

# mlp down-projection "wo" is 2-D while attention "wo" is 3-D; disambiguate
# by rank below.
MLP_WO_RULES = [("tp", None)]

# Serving (inference) ruleset: REDUCTION-FREE tensor parallelism. Every
# candidate shards an OUTPUT dim of its projection only, and infeasible
# leaves replicate instead of falling back to a contraction dim — so GSPMD
# never splits a dot's contraction across devices and never inserts a
# partial-sum reduce. Each output element is then computed by exactly one
# device with full-operand accumulation order, which (together with the
# all-gather hints in models/) makes the forward BITWISE IDENTICAL across
# mesh shapes — the serving engine's token-identity guarantee (DESIGN.md
# §11). Training keeps PARAM_RULES: there the Megatron-style contraction
# sharding halves the activation traffic and losslessness is not a gate.
SERVING_PARAM_RULES: Dict[str, List[Tuple[Optional[str], ...]]] = {
    "embedding": [("vocab", None)],
    "unembed": [("vocab", None)],
    "wq": [(None, "tp", None)],
    "wk": [(None, "tp", None)],
    "wv": [(None, "tp", None)],
    "wo": [(None, None, "tp")],          # attention 3-D: shard d_model out
    "bq": [(None, None)], "bk": [(None, None)], "bv": [(None, None)],
    "q_norm": [(None,)], "k_norm": [(None,)],
    "w_dq": [(None, "tp")],
    "w_uq": [(None, "tp", None)],
    "w_dkv": [(None, None)],
    "w_uk": [(None, "tp", None)],
    "w_uv": [(None, "tp", None)],
    "q_lora_norm": [(None,)], "kv_lora_norm": [(None,)],
    "wi": [(None, "tp")], "wg": [(None, "tp")],
    "we_i": [(None, None, "tp")],
    "we_g": [(None, None, "tp")],
    "we_o": [(None, None, "tp")],
    "router": [(None, None)],
    "in_proj": [(None, "tp")],
    "conv_w": [(None, None)], "conv_b": [(None,)],
    "A_log": [(None,)], "D": [(None,)], "dt_bias": [(None,)],
    "ssm_norm": [(None,)],
    "out_proj": [(None, "tp")],
    "scale": [(None,)], "bias": [(None,)],
    "gate": [()],
}
SERVING_MLP_WO_RULES = [(None, "tp")]

# Throughput serving ruleset: Megatron-style ROW PARALLELISM on the
# down-projections. The up-projections (wq/wk/wv/wi/wg/we_i/we_g/in_proj)
# keep the exact ruleset's column-parallel output-dim sharding, but the
# contraction-side weights — attention ``wo`` [H, hd, d], mlp ``wo``
# [f, d], moe ``we_o`` [E, f, d], ssm ``out_proj`` [e, d] — shard their
# CONTRACTION dim over model. Between the column and row halves the
# activation stays model-sharded (``ops.rowparallel_einsum``), each device
# contracts its local shard, and GSPMD realizes the replicated output with
# exactly ONE psum (all-reduce) per attention block and one per MLP —
# replacing the exact ruleset's full-activation all-gather before every
# contraction. The (tied) embedding table replicates instead of sharding
# over vocab: the exact ruleset's vocab-parallel lookup costs a per-step
# all-reduce and its vocab-sharded logits a per-step all-gather — shared
# overhead that at repro scale (V = 4 d_model) rivals the per-layer
# traffic; the throughput ruleset trades that table's memory for zero
# embed/logits collectives (a production vocab would re-shard it). The
# price of the row-parallel psum is accumulation order: tokens match an
# exact-ruleset engine only to tolerance, not bitwise — the throughput
# ruleset's OWN numerics are pinned at ROWPARALLEL_CHUNKS granularity so
# they stay reproducible across mesh sizes (DESIGN.md §13). Every other
# leaf is IDENTICAL to SERVING_PARAM_RULES — property tested in
# tests/test_tp_ruleset.py.
THROUGHPUT_PARAM_RULES: Dict[str, List[Tuple[Optional[str], ...]]] = {
    **SERVING_PARAM_RULES,
    "embedding": [(None, None)],         # replicated (tied lookup + logits)
    "unembed": [(None, None)],
    "wo": [("tp", None, None)],          # attention 3-D: shard heads (contraction)
    "we_o": [(None, "tp", None)],        # moe: shard d_ff (contraction)
    "out_proj": [("tp", None)],          # ssm: shard d_inner (contraction)
}
THROUGHPUT_MLP_WO_RULES = [("tp", None)]

# Canonical chunk count of the throughput ruleset's row-parallel psum: the
# down-projection contraction is ALWAYS split into this many f32-rounded
# bf16 partials (tp4 = one per device via GSPMD; tp1 emulates the combine
# in ops.rowparallel_einsum), so the ruleset's numerics are a property of
# the ruleset, not of the mesh it happens to run on. A contraction dim
# that this count does not divide replicates instead — on BOTH the weight
# side (here) and the activation side (ops.rowparallel_einsum), so the
# two fallbacks can never disagree.
ROWPARALLEL_CHUNKS = 4

# Leaves where the two serving rulesets intentionally differ: the
# contraction-side weights, plus the replicated embedding pair. Everything
# else must agree — tested.
CONTRACTION_LEAVES = ("wo", "we_o", "out_proj")
RULESET_DIVERGENT_LEAVES = CONTRACTION_LEAVES + ("embedding", "unembed")

AXIS_MAP = {"vocab": "model", "tp": "model"}


def _feasible(shape, cand, mesh_shape) -> bool:
    for dim, ax in zip(shape[-len(cand):] if cand else [], cand):
        if ax is None:
            continue
        sz = mesh_shape[AXIS_MAP[ax]]
        if dim % sz:
            return False
    return True


def _spec_for_leaf(path: str, shape, mesh: Mesh, fsdp: bool,
                   fsdp_axes=("data",), rule_set=None, mlp_wo=None,
                   throughput: bool = False) -> P:
    name = path.rsplit("/", 1)[-1]
    rules = (PARAM_RULES if rule_set is None else rule_set).get(name)
    if name == "wo":
        # attention wo ([H, hd, d]) lives under mixer/cross; everything
        # else named wo is an mlp down-projection ([f, d]). Rank cannot
        # disambiguate: the scan stack's leading repeats dim makes a
        # stacked mlp wo rank-3 — matching it against the attention rule
        # used to shard the STACK dim (surfacing as a hoisted per-step
        # weight reshard all-to-all in the tp audit)
        parent = path.rsplit("/", 2)[-2] if "/" in path else ""
        if parent not in ("mixer", "cross"):
            rules = MLP_WO_RULES if mlp_wo is None else mlp_wo
    if rules is None:
        rules = [tuple(None for _ in shape)]
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    # throughput contraction split is pinned at ROWPARALLEL_CHUNKS
    # granularity: a sharded dim the canonical chunk count does not divide
    # must replicate even if the (smaller) mesh would — keeps the weight
    # fallback aligned with ops.rowparallel_einsum's activation fallback
    chunked = throughput and name in CONTRACTION_LEAVES

    chosen = None
    for cand in rules:
        if len(cand) <= len(shape) and _feasible(shape, cand, mesh_shape):
            if chunked:
                dims = shape[len(shape) - len(cand):]
                if any(a == "tp" and d % ROWPARALLEL_CHUNKS
                       for a, d in zip(cand, dims)):
                    continue
            chosen = cand
            break
    if chosen is None:
        chosen = tuple(None for _ in shape)
    # pad leading dims (scan repeats axis etc.)
    full = [None] * (len(shape) - len(chosen)) + \
        [AXIS_MAP[a] if a else None for a in chosen]

    if fsdp and len(shape) >= 2:
        # ZeRO-3: shard the largest still-unsharded dim over the fsdp axes
        fsdp_size = int(np.prod([mesh_shape[a] for a in fsdp_axes]))
        free = [i for i, a in enumerate(full) if a is None]
        free = [i for i in free if shape[i] % fsdp_size == 0]
        if free:
            i = max(free, key=lambda j: shape[j])
            full[i] = fsdp_axes[0] if len(fsdp_axes) == 1 else tuple(fsdp_axes)
    return P(*full)


def _walk(tree, prefix=""):
    if isinstance(tree, dict):
        for k in tree:
            yield from _walk(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk(v, f"{prefix}/#{i}")
    elif tree is not None:
        yield prefix, tree


def _map_with_path(tree, fn, prefix=""):
    if isinstance(tree, dict):
        return {k: _map_with_path(v, fn, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, list):
        return [_map_with_path(v, fn, f"{prefix}/#{i}") for i, v in enumerate(tree)]
    if isinstance(tree, tuple):
        return tuple(_map_with_path(v, fn, f"{prefix}/#{i}") for i, v in enumerate(tree))
    if tree is None:
        return None
    return fn(prefix, tree)


def param_specs(params, mesh: Mesh, *, fsdp: bool = False,
                fsdp_axes: Sequence[str] = ("data",),
                expert_parallel: bool = False, serving: bool = False,
                ruleset: str = "exact"):
    """PartitionSpec tree matching ``params`` (arrays or ShapeDtypeStructs).

    ``expert_parallel=True`` flips the MoE rule to shard the experts dim
    over the model axis (the §Perf experiment). ``serving=True`` selects a
    serving ruleset chosen by ``ruleset``: ``"exact"`` (default) is the
    reduction-free ``SERVING_PARAM_RULES`` (output-dim tensor parallelism
    only — the bitwise-identity ruleset; DESIGN.md §11); ``"throughput"``
    is the Megatron-style ``THROUGHPUT_PARAM_RULES`` (row-parallel
    down-projections, one psum per block; DESIGN.md §13)."""
    if ruleset not in ("exact", "throughput"):
        raise ValueError(f"unknown serving ruleset {ruleset!r}")
    if serving and ruleset == "throughput":
        rules, mlp_wo = THROUGHPUT_PARAM_RULES, THROUGHPUT_MLP_WO_RULES
    elif serving:
        rules, mlp_wo = SERVING_PARAM_RULES, SERVING_MLP_WO_RULES
    else:
        rules, mlp_wo = PARAM_RULES, MLP_WO_RULES
    if expert_parallel:
        rules = dict(rules)
        rules["we_i"] = [("tp", None, None), (None, None, "tp")]
        rules["we_g"] = [("tp", None, None), (None, None, "tp")]
        rules["we_o"] = [("tp", None, None), (None, "tp", None)]
    return _map_with_path(
        params, lambda p, leaf: _spec_for_leaf(
            p, leaf.shape, mesh, fsdp, tuple(fsdp_axes), rules, mlp_wo,
            throughput=serving and ruleset == "throughput"))


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_spec(mesh: Mesh, batch: int, ndim: int) -> P:
    """Batch-leading data sharding; falls back to replication if the batch
    doesn't divide the data axes (e.g. batch=1 long-context)."""
    axes = _batch_axes(mesh)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    size = int(np.prod([mesh_shape[a] for a in axes]))
    if batch % size == 0:
        lead = axes if len(axes) > 1 else axes[0]
        return P(lead, *([None] * (ndim - 1)))
    # try data-only
    if "data" in mesh.axis_names and batch % mesh_shape["data"] == 0:
        return P("data", *([None] * (ndim - 1)))
    return P(*([None] * ndim))


def paged_cache_specs(caches, mesh: Mesh):
    """PartitionSpec tree for the KV pools (models.init_caches,
    serving/kv_pool.py).

    Attention leaves are shared block pools with no batch dim —
    ``[.., NB, bs, Hkv, hd]`` (scanned layers carry a leading repeats dim)
    — so the only shardable axis is the KV-head one, split over "model"
    when divisible (aligned with the head-sharded k/v projections of
    ``SERVING_PARAM_RULES``: the paged scatter and the per-head attention
    stay device-local). Quant ``*_scale`` siblings shard identically on
    their head dim; MLA ``ckv``/``ckv_scale`` pools and the block-size
    axis replicate; SSM leaves stay ``[B, ...]`` and replicate (they are
    O(1) per row). The block TABLES are host np arrays pushed replicated —
    every device must resolve every block index (DESIGN.md §11).
    """
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = mesh_shape.get("model", 1)

    def leaf_spec(path, leaf):
        shape = leaf.shape
        name = path.rsplit("/", 1)[-1]
        scanned = "/scan/" in path or path.startswith("/scan")
        off = 1 if scanned else 0        # leading repeats dim -> None
        spec = [None] * len(shape)
        if name in ("k", "v") and shape[off + 2] % model == 0:
            spec[off + 2] = "model"      # [.., NB, bs, Hkv, hd]
        elif name in ("k_scale", "v_scale") and shape[off + 2] % model == 0:
            spec[off + 2] = "model"      # [.., NB, bs, Hkv]
        # ckv / ckv_scale / conv / ssm: replicated
        return P(*spec)

    return _map_with_path(caches, leaf_spec)


def replicated_specs(tree):
    """A PartitionSpec tree replicating every leaf of ``tree`` — the
    serving draft model's sharding (small enough to live whole on every
    device; replication keeps its K sequential forwards collective-free)."""
    return _map_with_path(tree, lambda p, leaf: P())


def to_named(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
