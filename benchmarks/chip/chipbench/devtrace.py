"""The device trace: capture a window with JAX's profiler, then reduce it.

Device time is read from the profiler's ``XLA Ops`` lines, one per chip
plane (``/device:TPU:<n>``). The harness's own spans (``TraceAnnotation``
names that start with ``bench.``) come from the host plane, on the same
clock. The reduction takes plain lists, so it is tested on hand-built ones:

  busy      the union of the op intervals inside the window, per chip;
  idle gaps the rest of the window, split at the harness spans' edges and
            each piece labelled by the innermost span that holds it (what
            the host was doing);
  kernels   the union of the intervals of the Pallas kernels' ops
            (``tpu_custom_call`` in the op's HLO text);
  top ops   each op's self time (its time less that of the ops nested in
            it), summed by HLO instruction name.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
PALLAS_MARK = "tpu_custom_call"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    pallas: bool = False


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    """Device ops per chip plane, and the harness's host spans."""
    ops: Dict[str, List[Op]]
    spans: List[Span]


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace device and host (no Python function tracing) into log_dir."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def short_name(text: str) -> str:
    """``fusion.426 fusion`` for an op event named by its HLO text
    (``%fusion.426 = f32[896]{...} fusion(...), ...``); a Pallas kernel's
    custom call is marked ``tpu_custom_call``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    m = _OPCODE.search(rest)
    name = head.lstrip("%") + (" " + m.group(1) if m else "")
    return name + (" " + PALLAS_MARK if PALLAS_MARK in rest else "")


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.setdefault(plane.name, []).extend(
                    Op(short_name(e.name), e.start_ns,
                       e.start_ns + e.duration_ns, PALLAS_MARK in e.name)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, spans)


def merge(intervals: Sequence[Interval], lo: float,
          hi: float) -> List[Interval]:
    """Union of ``intervals`` clipped to [lo, hi], sorted and disjoint."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(merged: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` (sorted, disjoint) leaves."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(spans: Sequence[Span], t: float) -> str:
    """Name of the innermost span that holds time t."""
    holding = [s for s in spans if s.start_ns <= t < s.end_ns]
    if not holding:
        return "outside harness spans"
    inner = min(holding, key=lambda s: s.end_ns - s.start_ns)
    return inner.name[len(SPAN_PREFIX):]


def self_times(ops: Sequence[Op], lo: float, hi: float) -> Dict[str, float]:
    """Device time of each op name inside [lo, hi], less the time of the
    ops nested in it (a ``while`` holds its body's ops on the same line)."""
    out: Dict[str, float] = {}
    stack: List[list] = []           # [end, name, clipped duration]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    for o in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        while stack and stack[-1][0] <= o.start_ns:
            close(stack.pop())
        d = max(0.0, min(o.end_ns, hi) - max(o.start_ns, lo))
        if stack and o.end_ns <= stack[-1][0]:
            stack[-1][2] -= d
        stack.append([o.end_ns, o.name, d])
    while stack:
        close(stack.pop())
    return {k: v for k, v in out.items() if v > 0}


def reduce(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Busy and kernel seconds (averaged over chips), the ops that took the
    most device time and the idle time by what the host was doing, over
    the window [lo, hi] in ns."""
    if not trace.ops:
        return {}
    busy, pallas, per_op, idle = [], [], {}, {}
    edges = sorted({t for sp in trace.spans for t in (sp.start_ns, sp.end_ns)})
    for plane in sorted(trace.ops):
        ops = trace.ops[plane]
        merged = merge([(o.start_ns, o.end_ns) for o in ops], lo, hi)
        busy.append(covered(merged))
        pallas.append(covered(merge([(o.start_ns, o.end_ns) for o in ops
                                     if o.pallas], lo, hi)))
        for name, d in self_times(ops, lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + d
        for s, e in gaps(merged, lo, hi):
            i = bisect.bisect_right(edges, s)
            j = bisect.bisect_left(edges, e)
            cuts = [s] + edges[i:j] + [e]
            for a, b in zip(cuts, cuts[1:]):
                name = label(trace.spans, (a + b) / 2)
                idle[name] = idle.get(name, 0.0) + (b - a)
    n = len(busy)
    ns = 1e-9
    by_time = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    by_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        chips=n, window_s=(hi - lo) * ns, busy_s=sum(busy) / n * ns,
        pallas_s=sum(pallas) / n * ns,
        device_ops=[[k, v / n * ns] for k, v in by_time],
        idle_gaps=[[k, v / n * ns] for k, v in by_idle])
