"""The one traffic generator: a mix is a JSON file of parameters.

Keys of a traffic file (``traffic/<name>.json``):

  loop          "closed"  the queue is topped up to ``queue_depth`` requests
                          before every engine step (callers that each wait);
                "open"    requests fall due on an arrival schedule, whatever
                          the server does (independent users);
                "preload" every request is submitted and its prompt
                          prefilled during set-up; the window decodes.
  requests      how many requests are generated (closed: cycled in order;
                preload: all of them); open loops generate the arrivals of
                ``lead_s``, of the window and of ``drain_s``.
  block         closed and preload loops: requests are drawn in blocks of
                this many (default: one block of all), each block holding
                the same lengths in its own order, so that whatever number
                of whole blocks a window serves holds the same work.
  lead_s        open and closed loops: the loop runs this long before the
                window, so that the window starts with rows at staggered
                points of their requests; the lead-in's tokens and (open
                loop) latencies are not counted.
  prompt_len, output_len
                a length distribution: {"dist": "fixed", "value": v},
                {"dist": "uniform", "min": a, "max": b} or
                {"dist": "lognormal", "median": m, "sigma": s, "min": a,
                "max": b} (clipped to [min, max]).
  queue_depth   closed loop: requests kept queued (a number, or
                "max_batch" for the cell's engine batch).
  arrivals      open loop: {"process": "poisson", "rate_per_s": r}.
  drain_s       open loop: how long past the window the requests due in it
                may take to finish before the unfinished count as failed.
  tokens        {"source": "markov", "branching": 4, "zipf_a": 1.3,
                "determinism": 2.0, "corpus_seed": 0}: token content from a
                sparse Markov chain with Zipf-distributed starts.

Every seed gets the same set of lengths and inter-arrival gaps, drawn as
evenly spaced quantiles of their distributions, in an order and with token
content that the seed decides. Each stretch is drawn as a set of its own:
an open loop's lead-in, window and drain (``round(rate * seconds)``
arrivals each, their gaps scaled to fill the stretch), or a closed loop's
blocks. So seeds change which request comes when, not how much work a
window holds.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional, Tuple

import numpy as np

# streams of one run's seed, kept apart so adding one moves no other
_ORDER, _TOKENS, _ARRIVALS, _JAX = range(4)


@dataclasses.dataclass
class Request:
    """One generated request. ``due`` is seconds after the arrivals start
    (open loop), else None; ``counted`` marks an open loop's requests due
    in the window, whose latencies count."""
    index: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None
    counted: bool = False


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of a run's seed (any non-negative int)."""
    return np.random.default_rng([int(seed), stream])


def jax_seed(seed: int) -> int:
    """A 31-bit seed for JAX's PRNG drawn from a seed of any size."""
    return int(rng(seed, _JAX).integers(0, 2**31 - 1))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles (at (i + 0.5) / n) of a length
    distribution, as ints within its clips, in ascending order."""
    kind = dist["dist"]
    u = (np.arange(n) + 0.5) / n
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "uniform":
        lo, hi = int(dist["min"]), int(dist["max"])
        return lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
    if kind == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
        return np.clip(v, int(dist["min"]), int(dist["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the inter-arrival distribution."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(arrivals["rate_per_s"])


class Markov:
    """Token content: a sparse Markov chain with Zipf-distributed sequence
    starts (the repository's synthetic corpus, vectorised over a batch)."""

    def __init__(self, vocab_size: int, branching: int = 4,
                 zipf_a: float = 1.3, determinism: float = 2.0,
                 corpus_seed: int = 0):
        g = np.random.default_rng(corpus_seed)
        v, b = vocab_size, branching
        self.vocab_size = v
        self.succ = g.integers(0, v, size=(v, b))
        z = g.normal(size=(v, b))
        ez = np.exp((z - z.max(axis=1, keepdims=True)) * determinism)
        self.cdf = np.cumsum(ez / ez.sum(axis=1, keepdims=True), axis=1)
        w = np.arange(1, v + 1, dtype=np.float64) ** (-zipf_a)
        self.start = w / w.sum()

    def sample(self, g: np.random.Generator, batch: int,
               length: int) -> np.ndarray:
        out = np.empty((batch, length), np.int32)
        cur = g.choice(self.vocab_size, size=batch, p=self.start)
        out[:, 0] = cur
        last = self.succ.shape[1] - 1
        for t in range(1, length):
            u = g.random(batch)
            pick = np.minimum((u[:, None] > self.cdf[cur]).sum(axis=1), last)
            cur = self.succ[cur, pick]
            out[:, t] = cur
        return out


def _token_source(traffic: dict, vocab_size: int) -> Markov:
    spec = dict(traffic.get("tokens", {"source": "markov"}))
    if spec.pop("source", "markov") != "markov":
        raise ValueError("only the 'markov' token source exists")
    return Markov(vocab_size, **spec)


def _prompts(source: Markov, g: np.random.Generator,
             lengths: np.ndarray) -> List[np.ndarray]:
    """One prompt per length, drawn as a batch at the longest length."""
    if len(lengths) == 0:
        return []
    block = source.sample(g, len(lengths), int(lengths.max()))
    return [block[i, :n].copy() for i, n in enumerate(lengths)]


def stretches(traffic: dict, seconds: float) -> List[Tuple[int, float]]:
    """(requests, seconds) of each stretch drawn as one set: an open
    loop's lead-in, window and drain (seconds 0 elsewhere)."""
    if traffic["loop"] != "open":
        n = int(traffic["requests"])
        block = int(traffic.get("block", n))
        return [(min(block, n - i), 0.0) for i in range(0, n, block)]
    rate = float(traffic["arrivals"]["rate_per_s"])
    spans = (float(traffic.get("lead_s", 0.0)), float(seconds),
             float(traffic.get("drain_s", 0.0)))
    return [(max(1, round(rate * s)) if s > 0 else 0, s) for s in spans]


def request_count(traffic: dict, seconds: float) -> int:
    """How many requests one run generates."""
    return sum(n for n, _ in stretches(traffic, seconds))


def make_requests(traffic: dict, vocab_size: int, seed: int,
                  seconds: float) -> List[Request]:
    """The run's requests, in submission order (open loop: by due time)."""
    parts = stretches(traffic, seconds)
    n = sum(m for m, _ in parts)
    order = rng(seed, _ORDER)
    p_len = np.concatenate([order.permutation(
        quantiles(traffic["prompt_len"], m)) for m, _ in parts])
    o_len = np.concatenate([order.permutation(
        quantiles(traffic["output_len"], m)) for m, _ in parts])
    source = _token_source(traffic, vocab_size)
    tok = rng(seed, _TOKENS)
    prompts = _prompts(source, tok, p_len)
    due, counted = [None] * n, [False] * n
    if traffic["loop"] == "open":
        arrivals, start, due, counted = rng(seed, _ARRIVALS), 0.0, [], []
        for i, (m, span) in enumerate(parts):
            if m:
                # gaps scaled to fill the stretch: due in [start, start + span)
                g = arrivals.permutation(gaps(traffic["arrivals"], m))
                due += (start + (np.cumsum(g) - g) * (span / g.sum())).tolist()
                counted += [i == 1] * m
            start += span
    return [Request(i, prompts[i], int(o_len[i]), due[i], counted[i])
            for i in range(n)]
