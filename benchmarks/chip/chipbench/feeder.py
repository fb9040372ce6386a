"""Drive the serving engine: submit requests, step it in one-step slices,
and account for every request on the harness's clock.

The engine is the program's own: ``Engine.run(max_steps=...)`` runs its
synchronous dispatch/harvest loop, here one step at a time, and
``Engine.submit`` is called between slices. What the feeder reads from the
program: ``Engine.completions`` (tokens, ``ttft``, ``queue_wait``,
``wall_submitted``, ``wall_done``, ``generated``), the scheduler's
counters (``stats``) and, for requests still running, the scheduler's
per-slot host mirrors (``slots``, ``slot_last_n``, ``slot_limit``: the
committed token count of each live row as of its last harvest).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from . import traffic as traffic_mod

STAT_KEYS = ("steps", "committed", "accepted", "live_steps",
             "prefill_chunks", "prefill_tokens")


@dataclasses.dataclass
class Record:
    """One request on the harness's clock (perf_counter seconds)."""
    request: traffic_mod.Request
    due: Optional[float] = None          # open loop: when it fell due
    submitted: Optional[float] = None
    rid: Optional[int] = None            # the engine's request id
    counted: bool = False                # its latency counts (open loop)
    first_token: Optional[float] = None
    done: Optional[float] = None
    queue_wait: Optional[float] = None
    generated: int = 0
    tokens: Optional[np.ndarray] = None  # prompt + served, once finished

    @property
    def prompt_len(self) -> int:
        return len(self.request.prompt)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


class Feeder:
    """Feeds one engine from one list of generated requests."""

    def __init__(self, eng, requests: List[traffic_mod.Request]):
        self.eng = eng
        self.requests = requests
        self.records: List[Record] = []
        self.by_rid: Dict[int, Record] = {}
        self._next = 0              # next request of the list to submit
        self._seen = len(eng.completions)

    # ------------------------------------------------------------ submit
    def submit(self, req: traffic_mod.Request, due=None,
               counted=False) -> Record:
        from repro.serving.engine import SamplingParams
        rec = Record(req, due=due, counted=counted)
        rec.rid = self.eng.submit(req.prompt, params=SamplingParams(
            max_new=req.max_new, temperature=0.0))
        rec.submitted = time.perf_counter()
        self.records.append(rec)
        self.by_rid[rec.rid] = rec
        return rec

    def submit_cycled(self) -> Record:
        """The next request of the list, wrapping round at its end."""
        req = self.requests[self._next % len(self.requests)]
        self._next += 1
        return self.submit(req)

    def top_up(self, depth: int) -> None:
        """Closed loop: keep ``depth`` requests queued."""
        with _annotate("submit"):
            while len(self.eng.queue) < depth:
                self.submit_cycled()

    def submit_due(self, origin: float, now: float) -> None:
        """Open loop: submit every request due by ``now``; ``origin`` is
        when the arrivals started."""
        with _annotate("submit"):
            while self._next < len(self.requests):
                req = self.requests[self._next]
                due = origin + req.due
                if due > now:
                    break
                self._next += 1
                self.submit(req, due=due, counted=req.counted)

    def next_due(self, origin: float) -> Optional[float]:
        if self._next >= len(self.requests):
            return None
        return origin + self.requests[self._next].due

    # -------------------------------------------------------------- step
    def step(self) -> None:
        """One engine step (dispatch + harvest), then note completions."""
        with _annotate("engine_step"):
            self.eng.run(max_steps=self.eng.stats["steps"] + 1)
        with _annotate("count"):
            self._collect()

    def _collect(self) -> None:
        comps = self.eng.completions
        for c in comps[self._seen:]:
            rec = self.by_rid.get(c.rid)
            if rec is None:
                continue                       # a warm-up request
            rec.first_token = c.wall_submitted + c.ttft
            rec.done = c.wall_done
            rec.queue_wait = c.queue_wait
            rec.generated = c.generated
            rec.tokens = np.asarray(c.tokens)
        self._seen = len(comps)

    def has_work(self) -> bool:
        return self.eng.sched.has_work()

    # ---------------------------------------------------------- progress
    def committed(self) -> Dict[int, int]:
        """Committed tokens (prompt included) of every submitted request:
        finished ones from their completion, running ones from the
        scheduler's per-slot mirrors, queued ones at their prompt."""
        out = {rec.rid: (rec.prompt_len + rec.generated
                         if rec.done is not None else rec.prompt_len)
               for rec in self.records}
        for rep in self.eng.sched.replicas:
            for slot, req in enumerate(rep.slots):
                if req is not None and req.rid in out:
                    out[req.rid] = int(min(rep.slot_last_n[slot],
                                           rep.slot_limit[slot]))
        return out

    def stats(self) -> Dict[str, int]:
        return {k: int(self.eng.stats[k]) for k in STAT_KEYS}

    def live_outputs(self) -> Dict[int, np.ndarray]:
        """Prompt + committed tokens of every running request (one read of
        each replica's token buffer)."""
        out = {}
        for rep in self.eng.sched.replicas:
            if not any(s is not None for s in rep.slots):
                continue
            gen = rep.ex.read_gen()
            for slot, req in enumerate(rep.slots):
                if req is not None and req.rid in self.by_rid:
                    n = int(min(rep.slot_last_n[slot], rep.slot_limit[slot]))
                    out[req.rid] = gen[slot, :n].copy()
        return out
