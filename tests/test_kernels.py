"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
ref.py pure-jnp oracles (interpret=True executes the Pallas kernel bodies on
CPU) — flash, contiguous-operand decode, PARD training and SSD kernels. The
paged GQA kernels are in test_kernels_paged.py, the latent MLA kernel in
test_kernels_latent.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

from kernel_inputs import TOL, rand


@pytest.mark.parametrize("b,t,hq,hkv,d", [
    (1, 64, 4, 4, 32), (2, 128, 4, 2, 64), (1, 96, 8, 1, 32), (2, 50, 2, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, t, hq, hkv, d, dtype):
    q = rand(b, t, hq, d, k=1, dtype=dtype)
    k = rand(b, t, hkv, d, k=2, dtype=dtype)
    v = rand(b, t, hkv, d, k=3, dtype=dtype)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0), (0, 30.0),
                                            (32, 50.0)])
def test_flash_attention_variants(window, softcap):
    q, k, v = (rand(2, 64, 4, 32, k=i) for i in (1, 2, 3))
    out = ops.flash_attention(q, k, v, window=window, softcap=softcap,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("b,tq,hq,hkv,d,s", [
    (2, 9, 4, 2, 64, 256), (3, 1, 4, 4, 32, 128), (1, 5, 8, 2, 32, 100),
])
def test_decode_attention(b, tq, hq, hkv, d, s):
    q = rand(b, tq, hq, d, k=4)
    k = rand(b, s, hkv, d, k=5)
    v = rand(b, s, hkv, d, k=6)
    kv_len = jnp.asarray([s // 2 + 3 * i + tq for i in range(b)], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention(q, k, v, kv_len, q_pos, block_k=64)
    want = ref.decode_attention_ref(q, k, v, kv_len, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_attention_window():
    b, tq, h, d, s = 2, 3, 4, 32, 128
    q, k, v = rand(b, tq, h, d, k=7), rand(b, s, h, d, k=8), rand(b, s, h, d, k=9)
    kv_len = jnp.asarray([100, 80], jnp.int32)
    q_pos = (kv_len - tq)[:, None] + jnp.arange(tq)[None, :]
    out = ops.decode_attention(q, k, v, kv_len, q_pos, window=32, block_k=32)
    want = ref.decode_attention_ref(q, k, v, kv_len, q_pos, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("k_sub,r", [(3, 0.6), (4, 0.5)])
def test_pard_attention(k_sub, r):
    from repro.core.cod import CodConfig, pack_batch
    toks = np.random.default_rng(0).integers(0, 500, size=(2, 40))
    packed = pack_batch(toks, CodConfig(k=k_sub, r=r, r_min=0.2), 512, seed=0)
    seg = jnp.asarray(packed["segment"])
    base = jnp.asarray(packed["base"])
    t = seg.shape[1]
    q, k, v = rand(2, t, 2, 32, k=10), rand(2, t, 2, 32, k=11), rand(2, t, 2, 32, k=12)
    out = ops.pard_attention(q, k, v, seg, base, block_q=32)
    want = ref.pard_attention_ref(q, k, v, seg, base)
    live = np.asarray(seg > 0)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out) * live, np.asarray(want) * live,
                               atol=2e-5)


@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (1, 32, 2, 8, 4, 8), (2, 64, 3, 16, 8, 16), (1, 50, 2, 8, 8, 16),
])
def test_ssd_kernel(b, t, h, p, n, chunk):
    x = rand(b, t, h, p, k=13)
    dt = jax.nn.softplus(rand(b, t, h, k=14))
    A = -jnp.exp(rand(h, k=15) * 0.5)
    B = rand(b, t, n, k=16)
    C = rand(b, t, n, k=17)
    s0 = rand(b, h, p, n, k=18) * 0.1
    y, sf = ops.ssd_chunked(x, dt, A, B, C, s0, chunk=chunk)
    yr, sr = ref.ssd_ref(x, dt, A, B, C, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), atol=2e-4)


def test_ssd_kernel_matches_model_chunked_path():
    """The kernel and the model's jnp chunked scan must agree (they are the
    two production paths)."""
    from repro.models.ssm import ssd_scan_chunked
    b, t, h, p, n = 2, 48, 2, 8, 8
    x = rand(b, t, h, p, k=19)
    dt = jax.nn.softplus(rand(b, t, h, k=20))
    A = -jnp.exp(rand(h, k=21) * 0.5)
    B, C = rand(b, t, n, k=22), rand(b, t, n, k=23)
    y1, s1 = ops.ssd_chunked(x, dt, A, B, C, chunk=16)
    y2, s2 = ssd_scan_chunked(x, dt, A, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4)
