"""The program's own spans, scopes and counters in a device trace.

The serving engine names its host phases (``TraceAnnotation`` spans
``repro.<phase>``) and the parts of its fused step (``jax.named_scope``,
carried into each compiled op's ``op_name`` metadata), and counts per-phase
host nanoseconds and paged-KV pages in its scheduler's ``stats``. This
module reads them beside what ``devtrace`` reads, and leaves ``devtrace``'s
keys as they are. A TPU op event carries no metadata (its stats are its
device offset and duration), so an op's scope comes from the compiled
step's HLO text (``Executor.step_hlo()``), by instruction name: the trace
names each op by the instruction it runs. Instruction names are unique in
one program only; an op of another program that shares a name with one of
the step's (a small admission update) takes that one's scope.

  device_scopes  each op's self time summed by the outermost program scope
                 in its ``op_name``; ``unscoped`` for ops with none;
  idle_by_span   each idle piece of the window labelled by the innermost
                 span of either kind that holds it: a program phase
                 (``repro.dispatch``) or, outside them, a harness span
                 (``engine_step``), as ``devtrace`` labels it.

A program without these names reads as all ``unscoped`` and no phases; the
metric readers then return None.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional

from . import devtrace
from .devtrace import Op, Span, Trace

SPAN_PREFIX = "repro."
# the program's scope names (repro/serving/spans.py)
SCOPES = ("draft_forward", "target_forward", "accept", "commit",
          "tree_compact", "staged_mutations")
UNSCOPED = "unscoped"
# the program's counters (Scheduler.stats) that the readers use
STAT_KEYS = ("admit_ns", "sync_tables_ns", "dispatch_ns", "harvest_wait_ns",
             "harvest_ns", "kv_pages_live", "kv_pages_grid")
# an instruction of compiled HLO text and its op_name metadata
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
                     re.M)


@dataclasses.dataclass
class ScopedOp(Op):
    scope: str = UNSCOPED


@dataclasses.dataclass
class ProgramTrace(Trace):
    """``devtrace.Trace`` with scoped ops and the program's host spans."""
    program_spans: List[Span] = dataclasses.field(default_factory=list)


def scope_of(op_name: str) -> str:
    """The outermost program scope in an ``op_name`` path
    (``jit(fused)/target_forward/while/body/...`` -> ``target_forward``)."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """The program scope of each instruction of compiled HLO text that has
    one, by instruction name. Instructions the compiler adds (layout
    copies, buffer initialisations) carry no op_name and are left out."""
    out = {}
    for name, op_name in _HLO_OP.findall(hlo_text):
        scope = scope_of(op_name)
        if scope != UNSCOPED:
            out[name] = scope
    return out


def load(log_dir: str,
         op_scopes: Optional[Dict[str, str]] = None) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``log_dir``: ``devtrace.load``'s
    ops and harness spans, each op with its scope from ``op_scopes``
    (``hlo_scopes`` of the step's compiled text; None: all unscoped), and
    the program's spans."""
    op_scopes = op_scopes or {}
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    program: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE):
            for line in plane.lines:
                if line.name != devtrace.OPS_LINE:
                    continue
                for e in line.events:
                    name = devtrace.short_name(e.name)
                    ops.setdefault(plane.name, []).append(ScopedOp(
                        name, e.start_ns, e.start_ns + e.duration_ns,
                        devtrace.PALLAS_MARK in e.name,
                        op_scopes.get(name.split(" ", 1)[0], UNSCOPED)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(devtrace.SPAN_PREFIX):
                        spans.append(span)
                    elif e.name.startswith(SPAN_PREFIX):
                        program.append(span)
    return ProgramTrace(ops, spans, program)


def reduce(trace: ProgramTrace, lo: float, hi: float, top: int = 10) -> dict:
    """``devtrace.reduce`` of the window [lo, hi] (ns), with two keys more,
    [name, seconds] pairs averaged over chips, largest first. Both are
    ``devtrace.reduce`` over a relabelled trace: ``device_scopes`` its
    ``device_ops`` with each op named by its scope (self time: an op's
    time less that of the ops nested in it, which may lie in another
    scope), ``idle_by_span`` its ``idle_gaps`` with each program span
    among the harness spans, under its full name."""
    out = devtrace.reduce(trace, lo, hi, top)
    if not out:
        return out
    by_scope = Trace(
        {plane: [Op(getattr(o, "scope", UNSCOPED), o.start_ns, o.end_ns)
                 for o in ops] for plane, ops in trace.ops.items()},
        list(trace.spans) + [
            Span(devtrace.SPAN_PREFIX + s.name, s.start_ns, s.end_ns)
            for s in trace.program_spans])
    r = devtrace.reduce(by_scope, lo, hi, top=None)
    out["device_scopes"] = r["device_ops"]
    out["idle_by_span"] = r["idle_gaps"]
    return out
