"""The traffic generator: the same seed gives the same requests, every
seed the same lengths and gaps in another order, all within their clips."""
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
VOCAB = 1000


def _mix(name):
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    if mix["loop"] == "preload":
        mix["prompt_len"] = {"dist": "fixed", "value": 300}
    return mix


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.make_requests(mix, VOCAB, 2**33 + 5, 12.0)
    b = traffic.make_requests(mix, VOCAB, 2**33 + 5, 12.0)
    assert len(a) == len(b) == traffic.request_count(mix, 12.0)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips_and_tokens_in_vocab(name):
    mix = _mix(name)
    reqs = traffic.make_requests(mix, VOCAB, 3, 12.0)
    for key, get in (("prompt_len", lambda r: len(r.prompt)),
                     ("output_len", lambda r: r.max_new)):
        d = mix[key]
        lo = d.get("min", d.get("value"))
        hi = d.get("max", d.get("value"))
        assert all(lo <= get(r) <= hi for r in reqs)
    tokens = np.concatenate([r.prompt for r in reqs])
    assert tokens.min() >= 0 and tokens.max() < VOCAB


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_lengths_and_gaps_in_another_order(name):
    mix = _mix(name)
    a = traffic.make_requests(mix, VOCAB, 1, 12.0)
    b = traffic.make_requests(mix, VOCAB, 2, 12.0)
    block = mix.get("block", len(a)) if mix["loop"] != "open" else len(a)
    for i in range(0, len(a), block):
        assert sorted(len(r.prompt) for r in a[i:i + block]) == sorted(
            len(r.prompt) for r in b[i:i + block])
        assert sorted(r.max_new for r in a[i:i + block]) == sorted(
            r.max_new for r in b[i:i + block])
    if mix["loop"] == "open":
        lead = mix.get("lead_s", 0.0)
        wa, wb = ([r for r in x if r.counted] for x in (a, b))
        # the window holds the same number of arrivals and the same lengths
        assert len(wa) == len(wb) == round(mix["arrivals"]["rate_per_s"] * 12)
        assert all(lead <= r.due < lead + 12.0 for r in wa + wb)
        assert sorted(len(r.prompt) for r in wa) == sorted(
            len(r.prompt) for r in wb)
        assert sorted(r.max_new for r in wa) == sorted(r.max_new for r in wb)
        assert all(np.diff([r.due for r in a]) > 0)
        assert [r.due for r in wa] != [r.due for r in wb]
    assert any(not np.array_equal(x.prompt[:4], y.prompt[:4])
               for x, y in zip(a, b))


def test_closed_loop_blocks_hold_the_same_lengths():
    mix = _mix("reason")
    block = mix["block"]
    a, b = (traffic.make_requests(mix, VOCAB, s, 1.0) for s in (1, 2))
    assert len(a) == len(b) == mix["requests"]
    for i in range(0, len(a), block):
        assert sorted(r.max_new for r in a[i:i + block]) == sorted(
            r.max_new for r in b[i:i + block])
        assert sorted(len(r.prompt) for r in a[i:i + block]) == sorted(
            len(r.prompt) for r in b[i:i + block])
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_quantiles_follow_the_distribution():
    u = traffic.quantiles({"dist": "uniform", "min": 64, "max": 256}, 193)
    assert u.min() == 64 and u.max() == 256 and len(set(u)) == 193
    ln = traffic.quantiles({"dist": "lognormal", "median": 1024,
                            "sigma": 0.5, "min": 256, "max": 2048}, 1001)
    assert ln[500] == 1024 and ln.min() >= 256 and ln.max() == 2048
    gaps = traffic.gaps({"process": "poisson", "rate_per_s": 4.0}, 4000)
    assert gaps.mean() == pytest.approx(0.25, rel=0.01)


@pytest.mark.parametrize("key,value", [
    ("prompt_len", {"dist": "zipf", "a": 2.0}),
    ("arrivals", {"process": "gamma", "rate_per_s": 4.0, "cv": 3.0}),
])
def test_unknown_distributions_are_refused(key, value):
    mix = dict(_mix("chat"), **{key: value})
    with pytest.raises(ValueError, match="unknown"):
        traffic.make_requests(mix, VOCAB, 1, 12.0)


def test_jax_seed_fits_31_bits_for_large_seeds():
    for s in (0, 2**31 + 7, 2**40):
        assert 0 <= traffic.jax_seed(s) < 2**31
    assert traffic.jax_seed(2**40) == traffic.jax_seed(2**40)
