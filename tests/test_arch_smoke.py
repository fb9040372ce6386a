"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one forward and one train step on CPU; output shapes and
finiteness asserted. Also checks prefill+decode consistency against a single
cached forward over the KV pool (the property speculative decoding relies
on): every architecture's mixers — GQA, sliding-window, MLA, SSM, cross
attention — run on the one cache layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.core.adaptation import ar_loss
from repro.models import (encode, fake_frontend_embed, forward, init_caches,
                          init_params)
from repro.training.optimizer import AdamW


def _setup(name):
    cfg = get_config(name + "-smoke")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    enc_out = None
    fe = fake_frontend_embed(cfg, 2)
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, fe)
    elif cfg.cross_attn_period:
        enc_out = fe
    return cfg, params, tokens, enc_out, fe


@pytest.mark.parametrize("name", ASSIGNED)
def test_forward_shapes_and_finite(name):
    cfg, params, tokens, enc_out, _ = _setup(name)
    logits, _, aux = forward(params, cfg, tokens, enc_out=enc_out)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    # padded vocab ids can never win an argmax
    assert int(jnp.max(jnp.argmax(logits, -1))) < cfg.vocab_size


@pytest.mark.parametrize("name", ASSIGNED)
def test_train_step(name):
    cfg, params, tokens, enc_out, fe = _setup(name)
    opt = AdamW(lr=1e-3)
    state = opt.init(params)

    def loss_fn(p):
        loss, _ = ar_loss(p, cfg, tokens, dtype=jnp.float32,
                          frontend_embed=fe)
        return loss

    l0 = float(loss_fn(params))
    grads = jax.grad(loss_fn)(params)
    params2, state, om = opt.update(grads, state, params)
    l1 = float(loss_fn(params2))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert float(om["grad_norm"]) > 0.0
    assert l1 < l0 + 1e-3  # one step should not blow the loss up


@pytest.mark.parametrize("name", ASSIGNED)
def test_prefill_decode_consistency(name):
    cfg, params, tokens, enc_out, _ = _setup(name)
    T = tokens.shape[1]
    # two rows of 3 pages of 8 slots each, interleaved; block 0 = garbage
    bs, tables = 8, jnp.asarray([[1, 3, 5], [2, 4, 6]], jnp.int32)
    kw = dict(enc_out=enc_out, dtype=jnp.float32, block_tables=tables,
              kv_block_size=bs)
    caches = init_caches(cfg, 2, 7, bs, dtype=jnp.float32)
    full, _, _ = forward(params, cfg, tokens, caches=caches,
                         cache_pos=jnp.zeros(2, jnp.int32), **kw)
    caches = init_caches(cfg, 2, 7, bs, dtype=jnp.float32)
    lg, caches, _ = forward(params, cfg, tokens[:, :10], caches=caches,
                            cache_pos=jnp.zeros(2, jnp.int32), **kw)
    outs = [lg]
    for t in range(10, T):
        lg, caches, _ = forward(params, cfg, tokens[:, t:t + 1],
                                caches=caches,
                                cache_pos=jnp.full(2, t, jnp.int32), **kw)
        outs.append(lg)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepped),
                               atol=2e-3, rtol=2e-3)
