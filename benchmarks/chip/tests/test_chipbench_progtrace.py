"""The program's spans, scopes and counters in the benchmark: scope parsing,
device time by scope and idle time by program phase on a hand-built trace,
and the five readers of the program's counters and scopes, which read
nothing where the program or the trace has nothing."""
import pathlib
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import devtrace, progtrace, registry  # noqa: E402
from chipbench.devtrace import Span  # noqa: E402
from chipbench.progtrace import ProgramTrace, ScopedOp  # noqa: E402

READERS = ("host_ms.batch", "device_wait_ms.batch", "draft_fwd_share.batch",
           "target_fwd_share.batch", "kv_grid_live_share.batch")


def test_scope_is_the_outermost_program_scope_in_the_op_name():
    assert progtrace.scope_of(
        "jit(fused)/target_forward/while/body/closed_call") == "target_forward"
    assert progtrace.scope_of(
        "jit(fused)/draft_forward/while/body/commit/add") == "draft_forward"
    assert progtrace.scope_of("jit(fused)/lt") == "unscoped"
    assert progtrace.scope_of("") == "unscoped"


HLO = """\
%while.34 = (s32[], bf16[28,9,64]) while(%tuple.223), condition=%c, body=%b, metadata={op_name="jit(fused)/draft_forward/while" stack_frame_id=62}
%broadcast.54 = bf16[28,1185,64,2,128]{4,3,2,1,0:T(2,128)(2,1)} broadcast(%constant.211), dimensions={}
  ROOT %paged_decode_attention.19 = bf16[32,2,54,128]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused)/target_forward/while/body/closed_call/paged_decode_attention/pallas_call" stack_frame_id=9}
%fusion.3 = s32[32]{0} fusion(%n), kind=kLoop, metadata={op_name="jit(fused)/lt" stack_frame_id=1}
"""


def test_scopes_of_the_compiled_step_by_instruction_name():
    assert progtrace.hlo_scopes(HLO) == {
        "while.34": "draft_forward",
        "paged_decode_attention.19": "target_forward"}


def _trace():
    # chip 0: a layer loop of the draft (0-40) holding a kernel (10-30),
    # the verify kernel (50-80), an unscoped copy (80-85); the host
    # dispatches at 40-45 and blocks in the harvest at 85-100
    ops = {"/device:TPU:0": [
        ScopedOp("while.1 while", 0, 40, scope="draft_forward"),
        ScopedOp("paged_decode_attention.2 custom-call tpu_custom_call", 10,
                 30, pallas=True, scope="draft_forward"),
        ScopedOp("paged_decode_attention.3 custom-call tpu_custom_call", 50,
                 80, pallas=True, scope="target_forward"),
        ScopedOp("copy.4 copy", 80, 85)]}
    spans = [Span("bench.traced", -10, 110), Span("bench.engine_step", 0, 100)]
    program = [Span("repro.admit", 40, 42), Span("repro.dispatch", 42, 45),
               Span("repro.harvest_wait", 45, 80),
               Span("repro.harvest", 85, 100)]
    return ProgramTrace(ops, spans, program)


def test_device_time_by_scope_and_idle_time_by_phase():
    tr = _trace()
    r = progtrace.reduce(tr, 0, 100)
    ns = 1e-9
    scopes = dict(r["device_scopes"])
    assert scopes == pytest.approx({"draft_forward": 40 * ns,
                                    "target_forward": 30 * ns,
                                    "unscoped": 5 * ns})
    assert [k for k, _ in r["device_scopes"]] == [
        "draft_forward", "target_forward", "unscoped"]
    assert sum(scopes.values()) == pytest.approx(r["busy_s"])
    idle = dict(r["idle_by_span"])
    # idle 40-50: admit 40-42, dispatch 42-45, harvest_wait 45-50; 85-100
    # in the harvest's bookkeeping
    assert idle == pytest.approx({"repro.admit": 2 * ns,
                                  "repro.dispatch": 3 * ns,
                                  "repro.harvest_wait": 5 * ns,
                                  "repro.harvest": 15 * ns})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # devtrace's own keys are what devtrace gives
    base = devtrace.reduce(tr, 0, 100)
    assert {k: r[k] for k in base} == base


def test_idle_outside_program_phases_keeps_the_harness_label():
    tr = _trace()
    tr.program_spans = [s for s in tr.program_spans
                        if s.name != "repro.harvest"]
    idle = dict(progtrace.reduce(tr, 0, 100)["idle_by_span"])
    assert idle["engine_step"] == pytest.approx(15e-9)
    tr.program_spans = []            # a program without phases
    r = progtrace.reduce(tr, 0, 100)
    assert r["idle_by_span"] == r["idle_gaps"]


def test_nested_ops_in_another_scope_keep_their_own_time():
    ops = {"/device:TPU:0": [
        ScopedOp("while.1 while", 0, 100, scope="target_forward"),
        ScopedOp("fusion.2 fusion", 20, 50, scope="accept")],
        "/device:TPU:1": [ScopedOp("fusion.3 fusion", 0, 10, scope="accept")]}
    r = progtrace.reduce(ProgramTrace(ops, [], []), 0, 100)
    assert dict(r["device_scopes"]) == pytest.approx(
        {"target_forward": 70 / 2 * 1e-9, "accept": (30 + 10) / 2 * 1e-9})


def test_reduce_without_device_planes_reads_nothing():
    assert progtrace.reduce(ProgramTrace({}, [], []), 0, 10) == {}


def read(name, run):
    return registry.metric_reader(BENCH, name)(run)


def _run(trace=None, **stats):
    base = dict(steps=100, committed=0, accepted=0, live_steps=0,
                prefill_chunks=0, prefill_tokens=0)
    base.update(stats)
    return SimpleNamespace(stats=base, trace=trace, window_s=8.0)


def test_readers_of_the_program_counters_and_scopes():
    run = _run(trace=dict(busy_s=0.8, window_s=1.0, pallas_s=0.3,
                          device_scopes=[["draft_forward", 0.32],
                                         ["target_forward", 0.36],
                                         ["unscoped", 0.02]]),
               admit_ns=50_000_000, sync_tables_ns=30_000_000,
               dispatch_ns=120_000_000, harvest_wait_ns=7_500_000_000,
               harvest_ns=300_000_000, kv_pages_live=370,
               kv_pages_grid=1184)
    assert read("host_ms.batch", run) == pytest.approx(5.0)
    assert read("device_wait_ms.batch", run) == pytest.approx(75.0)
    assert read("draft_fwd_share.batch", run) == pytest.approx(40.0)
    assert read("target_fwd_share.batch", run) == pytest.approx(45.0)
    assert read("kv_grid_live_share.batch", run) == pytest.approx(
        100 * 370 / 1184)


def test_readers_read_nothing_without_counters_trace_or_steps():
    # a program without the counters and scopes (or a run without a trace)
    bare = _run(trace=dict(busy_s=0.8, window_s=1.0, pallas_s=0.3,
                           device_scopes=[["unscoped", 0.8]]))
    assert [read(n, bare) for n in READERS] == [None] * 5
    no_steps = _run(steps=0, admit_ns=1, sync_tables_ns=1, dispatch_ns=1,
                    harvest_wait_ns=1, harvest_ns=1, kv_pages_live=0,
                    kv_pages_grid=0)
    assert [read(n, no_steps) for n in READERS] == [None] * 5


def test_names_match_the_program():
    from repro.serving import spans
    assert progtrace.SCOPES == spans.SCOPES
    assert progtrace.SPAN_PREFIX == spans.PREFIX
    assert set(progtrace.STAT_KEYS) == set(spans.counters()) | {
        "kv_pages_live", "kv_pages_grid"}
