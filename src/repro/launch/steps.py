"""Step functions + input specs for the multi-pod dry-run and launchers.

Four lowered entry points per architecture (matching the assigned shapes):

  train_step    — AR loss fwd+bwd + AdamW update     (train_4k)
  prefill_step  — cached forward, last-only logits   (prefill_32k)
  decode_step   — ONE new token against a KV cache   (decode_32k, long_500k)
  verify_step   — PARD verification: K+1 drafted tokens in one pass against
                  the same cache (the paper's serving hot path; used by the
                  §Perf analysis and --mode pard_verify)

The cached steps run over the served KV layout: pools of ``KV_BLOCK``-token
pages (models.init_caches) read through per-row block tables, which ride
in the batch as ``tables``. Every input is a ShapeDtypeStruct
(``input_specs``) — nothing allocates.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..core.adaptation import ar_loss
from ..models import (KV_BLOCK, blocks_for, encode, forward,
                      frontend_embed_spec, init_caches, init_params)
from ..models.config import ModelConfig, SSM, scan_plan
from ..serving.kv_pool import default_num_blocks
from ..training.optimizer import AdamW

SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}

PARD_K = 8   # paper's K_train; verify window is K+1 tokens


def _has_ssm(cfg) -> bool:
    plan = scan_plan(cfg)
    return any(s.mixer == SSM for s in plan.prefix + plan.period)


# ---------------------------------------------------------------------------
# Step builders (pure functions of (params, ...); cfg is closed over)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt: AdamW, *, remat: bool = True):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = ar_loss(
                p, cfg, batch["tokens"], dtype=jnp.bfloat16, aux_weight=0.01,
                frontend_embed=batch.get("frontend_embed"), remat=remat)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state, om = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, **om}
    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, caches, batch):
        enc_out = _enc_out(params, cfg, batch)
        b = batch["tokens"].shape[0]
        logits, caches, _ = forward(
            params, cfg, batch["tokens"], caches=caches,
            cache_pos=jnp.zeros((b,), jnp.int32), enc_out=enc_out,
            last_only=True, block_tables=batch["tables"],
            kv_block_size=KV_BLOCK)
        return logits[:, -1], caches
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, window: int = 0):
    """One-token AR decode (the AR+ baseline's steady state)."""
    cfg = cfg if not window else _windowed(cfg, window)

    def decode_step(params, caches, batch):
        enc_out = _enc_out(params, cfg, batch)
        logits, caches, _ = forward(
            params, cfg, batch["tokens"], caches=caches,
            cache_pos=batch["cache_pos"], enc_out=enc_out,
            block_tables=batch["tables"], kv_block_size=KV_BLOCK)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, caches
    return decode_step


def make_verify_step(cfg: ModelConfig, *, k: int = PARD_K, window: int = 0):
    """PARD verification: K+1 tokens (last committed + K draft proposals)
    verified in ONE forward against the cache; returns per-position argmax
    (greedy acceptance happens host-side / in the engine)."""
    cfg = cfg if not window else _windowed(cfg, window)
    collect = _has_ssm(cfg)

    def verify_step(params, caches, batch):
        enc_out = _enc_out(params, cfg, batch)
        logits, caches, _ = forward(
            params, cfg, batch["tokens"], caches=caches,
            cache_pos=batch["cache_pos"], enc_out=enc_out, collect_ssm=collect,
            block_tables=batch["tables"], kv_block_size=KV_BLOCK)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, K+1]
        return tgt, caches
    return verify_step


def _windowed(cfg: ModelConfig, window: int) -> ModelConfig:
    """Long-context serving variant: every attention layer becomes
    sliding-window (the gemma2/jamba long_500k path; DESIGN.md §4)."""
    import dataclasses
    return dataclasses.replace(cfg, sliding_window=window,
                               local_global_period=0)


def _enc_out(params, cfg, batch):
    fe = batch.get("frontend_embed")
    if fe is None:
        return None
    if cfg.is_encoder_decoder:
        return encode(params, cfg, fe)
    return fe


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs only — no allocation)
# ---------------------------------------------------------------------------

def param_shapes(cfg: ModelConfig, dtype=None):
    sds = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                         jax.random.PRNGKey(0))
    if dtype is not None:
        sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), sds)
    return sds


def opt_state_shapes(cfg: ModelConfig, opt: AdamW):
    params = param_shapes(cfg)
    return jax.eval_shape(opt.init, params)


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16):
    """The KV pools of ``batch`` rows of ``max_len`` positions: the
    worst-case pool (every row full, plus the garbage block) of
    ``KV_BLOCK``-token pages."""
    num_blocks = default_num_blocks(batch, max_len, KV_BLOCK)
    return jax.eval_shape(functools.partial(
        init_caches, cfg, batch, num_blocks, KV_BLOCK, dtype=dtype))


def input_specs(cfg: ModelConfig, shape_name: str, *, mode: str = "default",
                k: int = PARD_K, cache_dtype=jnp.bfloat16,
                replicas: int = 1) -> Dict[str, Any]:
    """Returns {fn-kwargs-name: ShapeDtypeStruct} for the lowered step. A
    serving step holds one of ``replicas`` data-parallel replicas' share of
    the global batch (rounded up) and a pool for those rows."""
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]
    if kind != "train":
        b = -(-b // replicas)
    batch: Dict[str, Any] = {}
    fe = frontend_embed_spec(cfg, b)

    if kind == "train":
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if fe is not None:
            batch["frontend_embed"] = fe
        return {"batch": batch}

    # each row's block table: its own blocks_for(s) pages of the pool
    tables = jax.ShapeDtypeStruct((b, blocks_for(s, KV_BLOCK)), jnp.int32)
    if kind == "prefill":
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["tables"] = tables
        if fe is not None:
            batch["frontend_embed"] = fe
        return {"caches": cache_shapes(cfg, b, s, dtype=cache_dtype),
                "batch": batch}

    # decode / verify: q_len 1 or K+1 against a cache of s positions
    q = 1 if mode != "pard_verify" else k + 1
    batch["tokens"] = jax.ShapeDtypeStruct((b, q), jnp.int32)
    batch["cache_pos"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    batch["tables"] = tables
    if fe is not None:
        batch["frontend_embed"] = fe
    return {"caches": cache_shapes(cfg, b, s, dtype=cache_dtype),
            "batch": batch}
