"""The metric readers' arithmetic over all requests (a stalled request
included), the operation count against a hand count, and the spread."""
import pathlib
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import flops, registry, stats  # noqa: E402
from chipbench.feeder import Record  # noqa: E402
from chipbench.traffic import Request  # noqa: E402


def read(name, run):
    return registry.metric_reader(BENCH, name)(run)


def _record(due, first, done, generated, wait=0.0):
    return Record(Request(0, [1, 2, 3], generated), due=due, counted=True,
                  first_token=first, done=done, generated=generated,
                  queue_wait=wait)


def _run(records, **kw):
    base = dict(counted=records, window_s=10.0, output_tokens=2500,
                output_flops=0, setup_s=42.0, chips=1, trace=None,
                stats=dict(steps=400, committed=0, accepted=600,
                           live_steps=500, prefill_chunks=300,
                           prefill_tokens=2700))
    base.update(kw)
    return SimpleNamespace(**base)


def test_latency_percentiles_count_every_request_from_its_due_time():
    # 19 quick requests and one stalled behind the queue: it was due at 1 s
    # and its first token came at 9 s, so it sets the tail
    recs = [_record(due=0.1 * i, first=0.1 * i + 0.2, done=0.1 * i + 1.2,
                    generated=11) for i in range(19)]
    recs.append(_record(due=1.0, first=9.0, done=13.0, generated=5,
                        wait=7.5))
    run = _run(recs)
    ttfts = [200.0] * 19 + [8000.0]
    assert read("ttft_p95_ms", run) == pytest.approx(
        stats.percentile(ttfts, 95))
    assert read("ttft_p95_ms", run) > 200.0
    tpots = [100.0] * 19 + [1000.0]          # (done - first) / (gen - 1)
    assert read("tpot_p50_ms", run) == pytest.approx(100.0)
    assert read("tpot_p95_ms", run) == pytest.approx(
        stats.percentile(tpots, 95))
    assert read("queue_wait_p95_ms.chat", run) == pytest.approx(
        stats.percentile([0.0] * 19 + [7500.0], 95))


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([1.0, 2.0], 50) == pytest.approx(1.5)
    assert stats.percentile([], 95) is None


def test_rates_and_ratios_over_the_window():
    run = _run([])
    assert read("output_tok_s", run) == pytest.approx(250.0)
    assert read("setup_s", run) == pytest.approx(42.0)
    assert read("step_ms.batch", run) == pytest.approx(25.0)
    assert read("mean_accepted.batch", run) == pytest.approx(2.2)
    assert read("prefill_row_share.chat", run) == pytest.approx(37.5)


def test_trace_metrics_read_nothing_without_a_trace():
    run = _run([])
    for name in ("idle_share.batch", "attn_kernel_share.batch",
                 "idle_share.chat"):
        assert read(name, run) is None
    run = _run([], trace=dict(busy_s=0.75, window_s=1.0, pallas_s=0.3))
    assert read("idle_share.batch", run) == pytest.approx(25.0)
    assert read("attn_kernel_share.batch", run) == pytest.approx(40.0)


def test_mfu_divides_by_the_peak_of_the_device_kind():
    peak = registry.peaks(BENCH, "TPU v5 lite")["bf16_flops_per_s"]
    run = _run([], output_flops=int(peak * 10.0 * 0.02), chips=1,
               peak=lambda key: peak)
    assert read("mfu.batch", run) == pytest.approx(2.0)
    with pytest.raises(registry.RegistryError):
        registry.peaks(BENCH, "TPU v9 imaginary")


TINY = {"hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16,
        "num_hidden_layers": 3, "vocab_size": 50}


def test_flops_against_a_hand_count():
    # per layer: q 8x2x4=64, k and v 8x1x4=32 each, o 64, mlp 3x8x16=384
    per_layer = 64 + 32 + 32 + 64 + 384
    assert flops.matmul_flops(TINY) == 2 * (3 * per_layer + 8 * 50)
    # attention per key: QK and PV, 2 heads of 4, 3 layers, 2 ops a MAC
    assert flops.attention_flops_per_key(TINY) == 2 * 2 * 3 * 2 * 4
    # tokens at positions 10, 11, 12 attend 10, 11 and 12 keys
    assert flops.output_flops(TINY, 10, 13) == (
        3 * flops.matmul_flops(TINY) + 33 * flops.attention_flops_per_key(
            TINY))
    assert flops.output_flops(TINY, 7, 7) == 0


def test_spread_uses_statistics_quartiles():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    q1, med, q3 = __import__("statistics").quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)
