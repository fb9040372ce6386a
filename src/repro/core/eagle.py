"""EAGLE-style target-DEPENDENT draft head — the paper's main comparison
point (Fig. 1a, Tables 3/5).

A single transformer layer autoregresses over the target's last-layer
features: input at step t is ``W_fuse [e(x_t); f_{t-1}]`` where f is the
target hidden state (predicted recursively by the head beyond the committed
prefix), and logits reuse the target's unembedding. This captures EAGLE's
two defining properties relative to PARD:

  * higher information (it sees target features) but LOWER standalone
    accuracy than a real pretrained small LM (the paper's Fig. 1a), and
  * target-coupling: the head is trained per target model.

The draft phase is autoregressive (K sequential 1-layer passes) — cheap per
pass but K passes, unlike PARD's single pass.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..models import KV_BLOCK, forward, init_caches
from ..models import layers as L
from ..models import attention as attn
from ..models.config import ModelConfig

Array = jax.Array


def init_eagle(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "fuse": jax.random.normal(ks[0], (2 * d, d), jnp.float32) / math.sqrt(2 * d),
        "layer": {
            "norm1": L.init_rmsnorm(d),
            "mixer": attn.init_gqa(ks[1], cfg),
            "norm2": L.init_rmsnorm(d),
            "mlp": L.init_mlp(ks[2], d, cfg.d_ff, gated=True),
        },
        "out_norm": L.init_rmsnorm(d),
    }
    return p


def _layer_apply(lp, cfg, x, positions, cache, cache_pos, tables, block_size):
    h = L.rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
    y, new_cache = attn.gqa_apply(lp["mixer"], cfg, h, positions,
                                  cache=cache, cache_pos=cache_pos,
                                  block_tables=tables,
                                  kv_block_size=block_size)
    x = x + y
    h2 = L.rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
    x = x + L.mlp_apply(lp["mlp"], h2)
    return x, new_cache


def eagle_forward(eagle_params, target_params, cfg: ModelConfig, tokens,
                  feats, positions, *, cache=None, cache_pos=None,
                  tables=None, block_size=0):
    """tokens: [B, T] (x_t); feats: [B, T, D] (f_{t-1}, the target feature
    at the PREVIOUS position). ``cache``: the head's KV pool
    (attention.init_gqa_pool) read through ``tables``. Returns (logits,
    new_feats f̂_t, cache)."""
    e = L.embed_apply(target_params["embed"], tokens, cfg, dtype=feats.dtype)
    x = jnp.concatenate([e, feats], axis=-1)
    x = jnp.einsum("btd,de->bte", x, eagle_params["fuse"].astype(feats.dtype))
    x, new_cache = _layer_apply(eagle_params["layer"], cfg, x, positions,
                                cache, cache_pos, tables, block_size)
    f_hat = x
    h = L.rmsnorm_apply(eagle_params["out_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(target_params["embed"], h, cfg)
    return logits, f_hat, new_cache


def eagle_loss(eagle_params, target_params, cfg: ModelConfig, tokens,
               *, feat_weight: float = 0.1):
    """Distillation on a token batch: teacher-forced features from the
    target, CE to the target's argmax + feature regression (EAGLE recipe)."""
    t_logits, _, aux = forward(target_params, cfg, tokens, dtype=jnp.float32)
    f = aux["hidden"]                                  # [B, T, D]
    b, t = tokens.shape
    # head inputs at position i: token x_i, feature f_{i-1}
    feats_in = jnp.concatenate([jnp.zeros_like(f[:, :1]), f[:, :-1]], axis=1)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    logits, f_hat, _ = eagle_forward(eagle_params, target_params, cfg,
                                     tokens, feats_in, pos)
    # predict the target's next-token argmax (greedy distillation)
    labels = jnp.argmax(t_logits[:, 1:], axis=-1)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    reg = jnp.mean(jnp.abs(f_hat[:, :-1].astype(jnp.float32) -
                           f[:, 1:].astype(jnp.float32)))
    return ce + feat_weight * reg, {"ce": ce, "feat_l1": reg}


class EagleDecoder:
    """Greedy speculative decoding with an EAGLE head (chain, like the
    paper's Table 3 comparison). Target-side verification is identical to
    SpecDecoder; the draft phase is K sequential head passes. The target
    and the head keep their KV in pools of ``KV_BLOCK``-token pages, like
    SpecDecoder's."""

    def __init__(self, target_params, cfg: ModelConfig, eagle_params, *,
                 k: int = 4, max_len: int = 1024):
        self.tp, self.cfg, self.ep = target_params, cfg, eagle_params
        self.k, self.max_len = k, max_len
        self._step = None

    def _build_step(self):
        k, cfg, bs = self.k, self.cfg, KV_BLOCK
        from .acceptance import _row_take
        from .spec_decode import _row_write

        def step(gen, n, done, tcache, ecache, feat_prev, tables):
            # ---- draft: K sequential head passes --------------------------
            # The head's KV cache persists across iterations: entries for
            # ACCEPTED positions were computed from committed context, so the
            # usual cache_pos rollback semantics apply (rejected tail is
            # re-covered next iteration).
            cur = jnp.take_along_axis(gen, (n - 1)[:, None], axis=1)  # [B,1]
            feats = feat_prev[:, None]                                # [B,1,D]
            props = []
            epos = n - 1
            for j in range(k):
                lg, f_hat, ecache = eagle_forward(
                    self.ep, self.tp, cfg, cur.astype(jnp.int32), feats,
                    epos[:, None] + j, cache=ecache,
                    cache_pos=epos + j, tables=tables, block_size=bs)
                pj = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                props.append(pj)
                cur = pj[:, None]
                feats = f_hat[:, -1:]
            props = jnp.stack(props, axis=1)                          # [B,K]

            # ---- verify ---------------------------------------------------
            last = jnp.take_along_axis(gen, (n - 1)[:, None], axis=1)
            vin = jnp.concatenate([last.astype(jnp.int32), props], axis=1)
            logits, tcache, aux = forward(self.tp, cfg, vin, caches=tcache,
                                          cache_pos=n - 1,
                                          block_tables=tables,
                                          kv_block_size=bs)
            hidden = aux["hidden"]                                    # [B,K+1,D]
            tgt = jnp.argmax(logits[:, :k], axis=-1).astype(jnp.int32)
            match = (props == tgt).astype(jnp.int32)
            accepted = jnp.cumprod(match, axis=1)
            a = jnp.sum(accepted, axis=1)
            all_argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            commit_tok = _row_take(all_argmax, a)

            j = jnp.arange(k + 1)[None, :]
            props_ext = jnp.concatenate([props, props[:, -1:]], axis=1)
            vec = jnp.where(j < a[:, None], props_ext,
                            jnp.where(j == a[:, None], commit_tok[:, None], 0))
            old = jax.vmap(lambda g, p: jax.lax.dynamic_slice(
                g, (p,), (k + 1,)))(gen, n)
            vec = jnp.where(done[:, None], old, vec)
            gen = _row_write(gen, vec.astype(gen.dtype), n)
            # feature at the last committed token (input index a)
            feat_next = _row_take(hidden, a)
            feat_next = jnp.where(done[:, None], feat_prev, feat_next)
            new_n = jnp.where(done, n, n + a + 1)
            hist = jnp.sum(jnp.where(done[:, None], 0, accepted), axis=0)
            return (gen, new_n, tcache, ecache, feat_next,
                    jnp.where(done, 0, a), hist)

        return jax.jit(step)

    def generate(self, prompt, max_new: int):
        from .spec_decode import SpecStats, row_tables
        b, p = prompt.shape
        k, bs = self.k, KV_BLOCK
        L_buf = p + max_new + 2 * k + 2
        nb, tables = row_tables(b, L_buf, self.max_len, bs)
        tcache = init_caches(self.cfg, b, nb, bs)
        ecache = attn.init_gqa_pool(self.cfg, nb, bs)

        logits, tcache, aux = jax.jit(
            lambda t, c: forward(self.tp, self.cfg, t, caches=c,
                                 cache_pos=jnp.zeros((t.shape[0],), jnp.int32),
                                 block_tables=tables, kv_block_size=bs)
        )(prompt[:, :-1], tcache)
        hidden = aux["hidden"]                # f_0 .. f_{P-2}
        feat_prev = hidden[:, -1]             # f_{P-2}

        # head prefill: populate the head's KV cache over the prompt
        # (teacher-forced features, same layout as eagle_loss)
        feats_in = jnp.concatenate(
            [jnp.zeros_like(hidden[:, :1]), hidden[:, :-1]], axis=1)
        pos = jnp.broadcast_to(jnp.arange(p - 1)[None], (b, p - 1))
        _, _, ecache = jax.jit(
            lambda t, f, pp, c: eagle_forward(
                self.ep, self.tp, self.cfg, t, f, pp, cache=c,
                cache_pos=jnp.zeros((t.shape[0],), jnp.int32),
                tables=tables, block_size=bs)
        )(prompt[:, :-1], feats_in, pos, ecache)

        if self._step is None:
            self._step = self._build_step()

        gen = jnp.zeros((b, L_buf), jnp.int32)
        gen = gen.at[:, :p].set(prompt)
        n = jnp.full((b,), p, jnp.int32)
        done = jnp.zeros((b,), bool)
        target_n = p + max_new
        iters, acc_total, live_iters = 0, 0, 0
        acc_hist = jnp.zeros((k,), jnp.int32)
        while True:
            live = int(jnp.sum(~done))
            gen, n, tcache, ecache, feat_prev, a, hist = self._step(
                gen, n, done, tcache, ecache, feat_prev, tables)
            iters += 1
            live_iters += live
            acc_total += int(jnp.sum(a))
            acc_hist = acc_hist + hist
            done = n >= target_n
            if bool(jnp.all(done)) or iters > max_new + 2:
                break
        stats = SpecStats(iterations=iters,
                          tokens_generated=int(jnp.sum(
                              jnp.minimum(n, target_n) - p)),
                          draft_forwards=iters * k, target_forwards=iters,
                          accept_hist=jax.device_get(acc_hist),
                          acceptance_rate=acc_total / max(live_iters, 1) / k,
                          mean_accepted=acc_total / max(live_iters, 1) + 1.0)
        return gen[:, :target_n], stats
