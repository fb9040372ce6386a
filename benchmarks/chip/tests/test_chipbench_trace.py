"""The trace reduction on a hand-built trace: union busy time, idle share,
the Pallas kernels' share and idle gaps labelled by the harness's spans."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import devtrace  # noqa: E402
from chipbench.devtrace import Op, Span, Trace  # noqa: E402


def test_merge_unions_overlaps_and_clips():
    got = devtrace.merge([(5, 8), (0, 2), (1, 3), (7, 12)], 1, 10)
    assert got == [(1, 3), (5, 10)]
    assert devtrace.covered(got) == 7


def test_gaps_are_the_complement_inside_the_window():
    assert devtrace.gaps([(1, 3), (5, 10)], 0, 12) == [(0, 1), (3, 5),
                                                       (10, 12)]
    assert devtrace.gaps([], 0, 4) == [(0, 4)]


def test_label_takes_the_innermost_span():
    spans = [Span("bench.traced", 0, 100), Span("bench.engine_step", 10, 20),
             Span("bench.count", 20, 22)]
    assert devtrace.label(spans, 15) == "engine_step"
    assert devtrace.label(spans, 21) == "count"
    assert devtrace.label(spans, 50) == "traced"
    assert devtrace.label(spans, 150) == "outside harness spans"


def _trace():
    # chip 0: busy 0-40 (two overlapping ops) and 60-90 (a kernel);
    # chip 1: busy 10-30 only; the host submits at 40-60 and waits at 90+
    ops = {
        "/device:TPU:0": [Op("fusion.1", 0, 30), Op("fusion.2", 20, 40),
                          Op("custom-call.3", 60, 90, pallas=True)],
        "/device:TPU:1": [Op("fusion.1", 10, 30)],
    }
    spans = [Span("bench.traced", 0, 100), Span("bench.engine_step", 0, 40),
             Span("bench.submit", 40, 60), Span("bench.engine_step", 60, 90),
             Span("bench.wait_arrival", 90, 100)]
    return Trace(ops, spans)


def test_reduce_busy_idle_and_kernels():
    r = devtrace.reduce(_trace(), 0, 100)
    ns = 1e-9
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx((70 + 20) / 2 * ns)
    assert r["pallas_s"] == pytest.approx(30 / 2 * ns)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 20) / 2 * ns)
    assert ops["custom-call.3"] == pytest.approx(15 * ns)
    idle = dict(r["idle_gaps"])
    # chip 0 idles 40-60 (submit) and 90-100 (wait); chip 1 idles 0-10 and
    # 30-40 (engine step), 40-60 (submit), 60-90 (step), 90-100 (wait)
    assert idle["submit"] == pytest.approx((20 + 20) / 2 * ns)
    assert idle["wait_arrival"] == pytest.approx((10 + 10) / 2 * ns)
    assert idle["engine_step"] == pytest.approx((10 + 10 + 30) / 2 * ns)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_clips_to_the_window_and_ranks_by_time():
    r = devtrace.reduce(_trace(), 25, 75)
    ns = 1e-9
    assert r["busy_s"] == pytest.approx((15 + 15 + 5) / 2 * ns)
    assert [k for k, _ in r["device_ops"]] == ["fusion.2", "custom-call.3",
                                               "fusion.1"]


def test_reduce_without_device_planes_reads_nothing():
    assert devtrace.reduce(Trace({}, []), 0, 10) == {}


def test_self_time_leaves_out_nested_ops():
    ops = [Op("while.1 while", 0, 100), Op("fusion.2 fusion", 10, 30),
           Op("call.3 custom-call tpu_custom_call", 40, 50, pallas=True),
           Op("fusion.2 fusion", 60, 70)]
    got = devtrace.self_times(ops, 0, 100)
    assert got == {"while.1 while": 60, "fusion.2 fusion": 30,
                   "call.3 custom-call tpu_custom_call": 10}
    assert devtrace.self_times(ops, 0, 20) == {"while.1 while": 10,
                                                "fusion.2 fusion": 10}


def test_short_names_from_hlo_text():
    text = ("%closed_call.26 = bf16[16,2,112,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[16,81]{1,0:T(8,128)S(1)} %get-tuple-element.1), "
            'custom_call_target="tpu_custom_call"')
    assert devtrace.short_name(text) == (
        "closed_call.26 custom-call tpu_custom_call")
    text = ("%while.34 = (s32[]{:T(128)}, bf16[16,16,896]{2,1,0:T(8,128)}) "
            "while((s32[]{:T(128)}) %tuple.227), condition=%c, body=%b")
    assert devtrace.short_name(text) == "while.34 while"
    assert devtrace.short_name("jit_fused(96542)") == "jit_fused(96542)"
