"""Names and timing of the serving loop's phases (host) and of the fused
step's parts (device), on the profiler's clock.

Host: each phase of ``Engine.run`` is one ``jax.profiler.TraceAnnotation``
named ``repro.<phase>`` and adds its duration in integer nanoseconds to the
scheduler's ``stats["<phase>_ns"]``:

  admit         ``Scheduler.admit`` (metadata: how many it admitted)
  sync_tables   the block-table uploads and the KV peak accounting
  dispatch      ``Scheduler.dispatch`` up to the enqueued step (metadata:
                the step number)
  harvest_wait  the blocking batched ``device_get`` of one step's outputs
                (metadata: the step number of the dispatch it harvests)
  harvest       the result bookkeeping after the outputs arrive

Device: ``jax.named_scope`` names for the parts of the fused step. They
exist only at trace time and land in the compiled ops' ``op_name``
metadata, so the compiled step does the same work with them as without.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax

# inside a forward (models/attention.py, models/layers.py)
from ..models.scopes import LATENT_ATTENTION, MOE_EXPERTS, MOE_ROUTE

PREFIX = "repro."
PHASES = ("admit", "sync_tables", "dispatch", "harvest_wait", "harvest")

# device scopes of the fused step (core/spec_decode.py, serving/executor.py)
DRAFT = "draft_forward"        # the PARD mask-window (or VSD) draft forward
TARGET = "target_forward"      # the verify forward, or the AR forward
ACCEPT = "accept"              # the greedy rule and the sampled branch
COMMIT = "commit"              # the gen write, n/m and the state update
TREE_COMPACT = "tree_compact"  # compact_tree_caches
STAGED = "staged_mutations"    # the fused wrapper's retire/tree/limit fold
SCOPES = (DRAFT, TARGET, ACCEPT, COMMIT, TREE_COMPACT, STAGED)
LAYER_SCOPES = (LATENT_ATTENTION, MOE_ROUTE, MOE_EXPERTS)


def counters() -> Dict[str, int]:
    """The phase counters at zero, for the scheduler's ``stats``."""
    return {p + "_ns": 0 for p in PHASES}


class phase:
    """``with phase(stats, "dispatch", step=7) as ph:`` — one host span.

    Reads the clock once on entry (``ph.t0``) and once on exit (``ph.t1``,
    perf_counter_ns); the counter and any caller that needs the boundary
    (``Scheduler.host_overhead_ms``) share those readings. A phase that
    starts where another ends takes that one's reading as ``start_ns``.
    ``set(**meta)`` attaches metadata known only inside the phase."""

    __slots__ = ("_stats", "_key", "_ann", "t0", "t1")

    def __init__(self, stats: Dict, name: str, start_ns: Optional[int] = None,
                 **meta):
        self._stats, self._key = stats, name + "_ns"
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **meta)
        self.t0 = start_ns

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter_ns()
        return self

    def set(self, **meta) -> None:
        self._ann.set_metadata(**meta)

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._stats[self._key] += self.t1 - self.t0
