"""Per-request references for serving-engine completions: greedy rows
against ``SpecDecoder.generate_ar`` (and, for a row that meets a bf16
near-tie, the float32 cache-free forward), sampled rows against
``generate_spec`` on the engine's own row key."""
import jax.numpy as jnp
import numpy as np

from repro.models import forward

# float32 logit gap under which bf16 steps of different widths may rank two
# tokens either way: two bf16 steps (2**-6) at the tiny models' top logits
# (2-4). The near-ties met in tests read 0.028 and 0.014.
TIE = 0.03


def greedy_reference(dec, prompt, max_new):
    """AR greedy decoding of one request (the losslessness reference)."""
    return np.asarray(dec.generate_ar(jnp.asarray(prompt)[None],
                                      max_new)[0][0])


def assert_greedy_up_to_tie(dec, prompt, tokens, max_new):
    """``tokens`` (one engine row) leave AR greedy decoding only at a bf16
    near-tie: at the first position they differ, the float32 cache-free
    target forward over the shared prefix puts both choices within
    ``TIE``, and every token of the row is the float32 greedy choice up to
    ``TIE``. Independent of the decode steps: one uncached forward."""
    ar = greedy_reference(dec, prompt, max_new)
    tokens = np.asarray(tokens)
    diff = np.nonzero(ar != tokens)[0]
    assert diff.size, "the row is AR's: no near-tie to check"
    logits = np.asarray(forward(dec.tp, dec.tc, jnp.asarray(tokens)[None],
                                dtype=jnp.float32)[0][0])
    t = int(diff[0])
    assert abs(logits[t - 1, ar[t]] - logits[t - 1, tokens[t]]) < TIE
    p = len(prompt)
    gen = logits[p - 1:-1]
    deficit = gen.max(-1) - gen[np.arange(len(gen)), tokens[p:]]
    assert deficit.max() < TIE, deficit


def sampled_reference(dec, prompt, max_new, rid, seed, tree_idx=None):
    """``generate_spec`` of one request on the PRNG stream the engine gives
    request ``rid`` under engine seed ``seed``: the engine derives a row's
    key from (seed, rid), ``generate_*`` from (seed, batch row), so the
    request runs as row ``rid`` of ``rid + 1`` copies of its prompt (rows
    are independent: the other copies change nothing in it)."""
    batch = jnp.asarray(np.stack([prompt] * (rid + 1)))
    idx = None if tree_idx is None else [tree_idx] * (rid + 1)
    out, _ = dec.generate_spec(batch, max_new, mode="pard", seed=seed,
                               tree_idx=idx)
    return np.asarray(out[rid])
