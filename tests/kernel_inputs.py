"""Shared inputs of the kernel tests: seeded random operands, the
per-dtype tolerances, paged pools with shuffled tables, and the edge fills
of the page sweep."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

KEY = jax.random.PRNGKey(42)


def rand(*shape, k=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32
                             ).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def paged_setup(b, hkv, d, bs, mbs, key=0):
    """A pool with rows owning interleaved (non-monotone) blocks, plus the
    equivalent contiguous cache for cross-layout parity checks."""
    nb = 1 + b * mbs
    k_pages = rand(nb, bs, hkv, d, k=key + 1)
    v_pages = rand(nb, bs, hkv, d, k=key + 2)
    perm = np.random.default_rng(key).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mbs), jnp.int32)
    k_cont = ref.gather_pages(k_pages, tables)
    v_cont = ref.gather_pages(v_pages, tables)
    return k_pages, v_pages, tables, k_cont, v_cont


def sweep_fills(bs, group, mbs):
    """One row per edge of the sweep: empty, one slot, a page less one, a
    page, a group less one, a group, a group and one, the whole table."""
    return [0, 1, bs - 1, bs, group * bs - 1, group * bs, group * bs + 1,
            mbs * bs]


def seen_queries(q_pos, kv_len):
    """[B, Tq] queries that see at least one key (the rest are padding:
    the kernels write zeros there, the oracle an average)."""
    return np.asarray((q_pos >= 0) & (kv_len[:, None] > 0))
