"""Roofline derivation from the compiled dry-run artifact.

Three terms per (arch, shape, mesh), in seconds (TPU v5e constants):

  compute_s    = HLO_FLOPs / (chips * 197 TFLOP/s bf16)
  memory_s     = HLO_bytes / (chips * 819 GB/s HBM)
  collective_s = collective_bytes / (chips * 50 GB/s ICI)

cost_analysis() reports whole-program FLOPs/bytes (already accounting for the
SPMD partitioning — the lowered module is the per-device program times the
replica count; XLA reports the global module, so we divide by chip count).
collective_bytes is parsed from the compiled HLO text: the result bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op.

Also derives MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs.
"""
from __future__ import annotations

import re
from typing import Any, Dict

from ..models.config import MLP_MOE, ModelConfig, layer_plan
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_census(hlo_text: str) -> Dict[str, Any]:
    """Sum result bytes per collective kind from compiled HLO."""
    census = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs_rhs = ls.split("=", 1)
        rhs = lhs_rhs[1].strip()
        for kind in _COLLECTIVES:
            # match "<type> <kind>(" — kind must be the op, not a substring
            m = re.match(r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
                         + kind + r"(?:-start|-done)?\(", rhs)
            if m:
                # -done ops repeat the -start result; count only starts & sync
                if kind + "-done(" in rhs:
                    census[kind]["count"] += 0
                else:
                    census[kind]["count"] += 1
                    census[kind]["bytes"] += _shape_bytes(m.group(1))
                break
    census["total_bytes"] = sum(v["bytes"] for k, v in census.items()
                                if isinstance(v, dict))
    return census


def scan_correction(cfg: ModelConfig) -> float:
    """XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, not
    times its trip count — verified empirically: gemma2-27b train reports
    ~23x fewer FLOPs than 6·N·D, matching its 23 scanned periods. All
    HLO-derived terms are scaled by (prefix + repeats*period)/(prefix +
    period) to undo this. Embed/unembed live outside the scan so this is a
    slight over-correction for them (documented approximation)."""
    from ..models.config import scan_plan
    plan = scan_plan(cfg)
    body = len(plan.prefix) + len(plan.period)
    total = len(plan.prefix) + plan.n_repeats * len(plan.period)
    return total / max(body, 1)


def model_flops_per_token(cfg: ModelConfig) -> float:
    """2·N_active per token — the forward-pass estimate (training applies
    a 3x fwd+bwd multiplier in roofline_terms)."""
    n_active = 0.0
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    for spec in layer_plan(cfg):
        if spec.mixer in ("attn_global", "attn_local"):
            n_active += d * cfg.n_heads * hd * 2          # wq + wo
            n_active += d * cfg.n_kv_heads * hd * 2       # wk + wv
        elif spec.mixer == "attn_mla":
            r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
            dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            if r_q:
                n_active += d * r_q + r_q * cfg.n_heads * (dn + dr)
            else:
                n_active += d * cfg.n_heads * (dn + dr)
            n_active += d * (r_kv + dr)
            n_active += r_kv * cfg.n_heads * (dn + dv)
            n_active += cfg.n_heads * dv * d
        elif spec.mixer == "attn_cross":
            n_active += d * cfg.n_heads * hd * 2
            n_active += d * cfg.n_kv_heads * hd * 2
        elif spec.mixer == "ssm":
            d_in = cfg.ssm_inner
            n_active += d * (2 * d_in + 2 * cfg.ssm_state + cfg.ssm_nheads)
            n_active += d_in * d
        if spec.mlp == "dense":
            f = cfg.first_dense_d_ff or cfg.d_ff
            n_active += d * f * (3 if cfg.mlp_gated else 2)
        elif spec.mlp == MLP_MOE:
            f = cfg.moe_d_ff or cfg.d_ff
            n_active += d * f * 3 * (cfg.moe_top_k + cfg.moe_num_shared)
            n_active += d * cfg.moe_num_experts            # router
    n_active += d * cfg.padded_vocab                       # unembed
    return 2.0 * n_active


def tokens_processed(cfg: ModelConfig, shape: str, mode: str) -> float:
    from .steps import PARD_K, SHAPES
    sh = SHAPES[shape]
    if sh["kind"] == "train":
        return sh["global_batch"] * (sh["seq_len"] - 1)
    if sh["kind"] == "prefill":
        return sh["global_batch"] * sh["seq_len"]
    q = PARD_K + 1 if mode == "pard_verify" else 1
    return sh["global_batch"] * q


def roofline_terms(rec: Dict[str, Any], cfg: ModelConfig, shape: str
                   ) -> Dict[str, Any]:
    """NOTE: jax's compiled.cost_analysis() on an SPMD-partitioned module
    reports PER-DEVICE flops/bytes (verified empirically: an 8-way sharded
    matmul reports ~1/8 the flops). The collective shapes in the partitioned
    HLO are likewise per-device. So each term is simply value / per-chip
    rate — no further division by chip count."""
    chips = 1
    for m in rec["mesh"]:
        chips *= m
    # Empirically (see EXPERIMENTS.md §Roofline caveats): serve-step records
    # count the scanned while body fully, but TRAIN records (remat inside
    # scan) under-count by roughly the repeat count. The correction applies
    # to train only; the analytic compute term below is authoritative for
    # the compute axis either way.
    is_train = shape == "train_4k"
    corr = scan_correction(cfg) if is_train else 1.0
    flops = rec.get("flops", 0.0) * corr       # per device (diagnostic only)
    byts = rec.get("bytes_accessed", 0.0)      # raw HLO traffic
    coll = rec.get("collectives", {}).get("total_bytes", 0)

    toks = tokens_processed(cfg, shape, rec.get("mode", "default"))
    mult = 3.0 if is_train else 1.0                     # fwd+bwd
    mflops = model_flops_per_token(cfg) * toks * mult   # global, analytic
    # compute term: the ANALYTIC model FLOPs per chip (the roofline
    # definition); the HLO-derived term is kept for diagnostics
    compute_s = mflops / chips / PEAK_FLOPS_BF16
    memory_s = byts / HBM_BW
    collective_s = coll / ICI_BW
    terms = dict(compute_s=compute_s, memory_s=memory_s,
                 collective_s=collective_s)
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.replace("_s", "")
    terms["scan_correction"] = corr
    terms["compute_s_hlo"] = flops / PEAK_FLOPS_BF16
    hlo_global = flops * chips
    terms["model_flops"] = mflops
    terms["useful_compute_ratio"] = (mflops / hlo_global) \
        if hlo_global else 0.0
    terms["tokens"] = toks

    # Analytic HBM floor for serving steps (weights + KV cache streamed once
    # per step). XLA-CPU "bytes accessed" reflects CPU fusion choices, which
    # can both over-count (materialised f32 attention scores) and under-count
    # (fully fused 1-token attention) relative to TPU HBM traffic — so the
    # table reports max(HLO, analytic) as memory_s and keeps both.
    from .steps import SHAPES
    sh = SHAPES[shape]
    if sh["kind"] == "decode":
        model_axis = rec["mesh"][-1]
        wb = _param_bytes(cfg) / model_axis             # bf16, TP-sharded
        cb = _kv_cache_bytes_per_device(cfg, sh["global_batch"],
                                        sh["seq_len"], rec["mesh"])
        analytic = (wb + cb) / HBM_BW
        terms["memory_s_analytic"] = analytic
        terms["memory_s_hlo"] = terms["memory_s"]
        terms["memory_s"] = max(terms["memory_s"], analytic)
        dom = max(("compute_s", "memory_s", "collective_s"),
                  key=lambda k: terms[k])
        terms["dominant"] = dom.replace("_s", "")
    return terms


def _param_bytes(cfg: ModelConfig) -> float:
    """Approximate serving weight bytes (bf16)."""
    per_tok = model_flops_per_token(cfg) / 6.0          # = N_active
    # active != total for MoE; scale up by expert ratio
    if cfg.moe_num_experts:
        f = cfg.moe_d_ff or cfg.d_ff
        routed_active = cfg.d_model * f * 3 * cfg.moe_top_k
        routed_total = cfg.d_model * f * 3 * cfg.moe_num_experts
        per_tok += (routed_total - routed_active) * \
            sum(1 for s in layer_plan(cfg) if s.mlp == MLP_MOE)
    return per_tok * 2.0


def _kv_cache_bytes_per_device(cfg: ModelConfig, batch, seq, mesh) -> float:
    """KV bytes one decode step must stream, per device, as data-parallel
    serving holds it: each replica (one row of "model" devices) serves its
    share of the rows, rounded up, at their full length; kv heads over
    model when divisible, else REPLICATED (paged_cache_specs — the
    command-r-35b kv=8 case reads its replica's whole pool on every
    device)."""
    model = mesh[-1]
    data = 1
    for m in mesh[:-1]:
        data *= m
    b_local = -(-batch // data)
    s_local = seq
    total = 0.0
    for spec in layer_plan(cfg):
        if spec.mixer in ("attn_global", "attn_local"):
            hkv = cfg.n_kv_heads
            h_local = hkv / model if hkv % model == 0 else hkv
            if spec.mixer == "attn_local" and cfg.sliding_window:
                s_eff = min(s_local, cfg.sliding_window)
            else:
                s_eff = s_local
            total += 2 * b_local * s_eff * h_local * cfg.resolved_head_dim * 2
        elif spec.mixer == "attn_mla":
            total += b_local * s_local * \
                (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
        elif spec.mixer == "ssm":
            n_, h_, p_ = cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
            total += b_local * h_ * p_ * n_ * 4
    return total
