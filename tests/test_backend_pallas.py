"""End-to-end backend equivalence: the model forward with the Pallas
attention backend (interpret mode on CPU) must match the XLA reference path
— the integration-level counterpart of the per-kernel oracle tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_caches, init_params
from repro.models.attention import set_attention_backend


@pytest.fixture(autouse=True)
def _restore_backend():
    yield
    set_attention_backend(None)


@pytest.mark.parametrize("arch", ["tiny-target", "gemma2-27b"])
def test_forward_backend_equivalence(arch):
    cfg = get_config(arch + "-smoke") if arch != "tiny-target" \
        else get_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    set_attention_backend("xla")
    ref, _, _ = forward(params, cfg, tokens, dtype=jnp.float32)
    set_attention_backend("pallas")
    out, _, _ = forward(params, cfg, tokens, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_decode_backend_equivalence():
    """A window wider than the paged kernel's (a 40-token prefill) reads
    the pool through the gathered per-row view and the contiguous-operand
    decode kernel; the decode after it through the paged kernel. Both must
    match the XLA path."""
    cfg = get_config("tiny-target")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                cfg.vocab_size)
    tables = jnp.asarray([[1, 3, 5, 7, 9, 11], [2, 4, 6, 8, 10, 12]],
                         jnp.int32)
    kw = dict(block_tables=tables, kv_block_size=8, dtype=jnp.float32)

    def run():
        caches = init_caches(cfg, 2, 13, 8, dtype=jnp.float32)
        pre, caches, _ = forward(params, cfg, tokens, caches=caches,
                                 cache_pos=jnp.zeros(2, jnp.int32), **kw)
        lg, _, _ = forward(params, cfg, tokens[:, :1], caches=caches,
                           cache_pos=jnp.full(2, 40, jnp.int32), **kw)
        return jnp.concatenate([pre, lg], axis=1)

    set_attention_backend("xla")
    ref = run()
    set_attention_backend("pallas")
    out = run()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_paged_decode_backend_equivalence():
    """Paged-cache decode through the model forward: the Pallas paged
    kernel (block-table indirection) must match the XLA gather path."""
    from repro.serving import kv_pool
    cfg = get_config("tiny-target")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    tables = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)

    def run():
        caches = kv_pool.init_paged_caches(cfg, 2, num_blocks=9, block_size=8,
                                           dtype=jnp.float32)
        _, caches, _ = forward(params, cfg, tokens, caches=caches,
                               cache_pos=jnp.zeros(2, jnp.int32),
                               block_tables=tables, kv_block_size=8,
                               dtype=jnp.float32)
        lg, _, _ = forward(params, cfg, tokens[:, :1], caches=caches,
                           cache_pos=jnp.full(2, 16, jnp.int32),
                           block_tables=tables, kv_block_size=8,
                           dtype=jnp.float32)
        return lg

    set_attention_backend("xla")
    ref = run()
    set_attention_backend("pallas")
    out = run()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def _has_pallas_call(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _oracle_cases():
    """(name, oracle, args) for every attention oracle of kernels/ref.py,
    at a tiny GQA shape with a paged pool and a 4-slot verify window."""
    from repro.kernels import ref
    b, tq, hq, hkv, d, bs, mbs = 2, 4, 4, 2, 8, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, tq, hq, d))
    kc = jax.random.normal(ks[1], (b, bs * mbs, hkv, d))
    kp = jax.random.normal(ks[2], (1 + b * mbs, bs, hkv, d))
    tables = jnp.arange(1, 1 + b * mbs, dtype=jnp.int32).reshape(b, mbs)
    kv_len = jnp.full((b,), 12, jnp.int32)
    q_pos = 8 + jnp.broadcast_to(jnp.arange(tq)[None], (b, tq))
    win = jnp.full((b,), 8, jnp.int32)
    anc = jnp.broadcast_to(jnp.uint32([1, 3, 5, 9])[None], (b, tq))
    seg = jnp.ones((b, tq), jnp.int32)
    base = jnp.broadcast_to(jnp.arange(tq)[None], (b, tq))
    return [
        ("flash", ref.flash_attention_ref, (q, q[:, :, :hkv], q[:, :, :hkv])),
        ("decode", ref.decode_attention_ref, (q, kc, kc, kv_len, q_pos)),
        ("decode_paged", ref.decode_attention_paged_ref,
         (q, kp, kp, tables, kv_len, q_pos)),
        ("tree", ref.tree_attention_ref,
         (q, kc, kc, kv_len, q_pos, win, anc)),
        ("tree_paged", ref.tree_attention_paged_ref,
         (q, kp, kp, tables, kv_len, q_pos, win, anc)),
        ("pard", ref.pard_attention_ref, (q, q, q, seg, base)),
    ]


@pytest.mark.parametrize("name", [c[0] for c in _oracle_cases()])
def test_oracles_never_run_a_kernel(name):
    """The kernels/ref.py oracles are the jnp reference whatever the
    backend: with the kernels pinned on, an oracle that reached a kernel
    would compare the kernel with itself."""
    _, oracle, args = next(c for c in _oracle_cases() if c[0] == name)
    set_attention_backend("pallas")
    assert not _has_pallas_call(oracle, *args)


def test_platform_default_runs_kernels_on_cached_paths_only(monkeypatch):
    """Unpinned on a TPU, the serving steps' cached forwards run the
    kernels and an uncached forward (training, differentiated) keeps the
    jnp path, so ``jax.grad`` of a loss never meets a pallas_call."""
    from repro.core.adaptation import ar_loss
    set_attention_backend(None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("tiny-target")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)

    tables = jnp.asarray([[1, 3], [2, 4]], jnp.int32)

    def decode(params, caches):
        return forward(params, cfg, tokens[:, :1], caches=caches,
                       cache_pos=jnp.full(2, 15, jnp.int32),
                       block_tables=tables, kv_block_size=8)[0]

    caches = init_caches(cfg, 2, 5, 8)
    assert _has_pallas_call(decode, params, caches)
    assert not _has_pallas_call(
        lambda p: forward(p, cfg, tokens)[0], params)
    assert not _has_pallas_call(
        jax.grad(lambda p: ar_loss(p, cfg, tokens)[0]), params)
