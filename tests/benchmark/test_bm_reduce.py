"""Metric arithmetic on a synthetic request log, the /metrics reader,
and the JSON kind of per-layer metric."""

import json
import os

import pytest

from benchmark.lib import layer_metrics, prom
from benchmark.lib import reduce as R

from . import helpers as H


def rec(due, first, n_tokens, burst=1, step=0.02, sent=None, **kw):
    """A request whose tokens arrive ``burst`` at a time every
    ``step`` seconds."""
    t, chunk_t = first, []
    for i in range(n_tokens):
        if i and i % burst == 0:
            t += step
        chunk_t.append(t)
    return dict({"due": due, "sent": due if sent is None else sent,
                 "chunk_t": chunk_t, "completion_tokens": n_tokens,
                 "output_tokens": n_tokens, "end": chunk_t[-1]}, **kw)


def test_percentile_matches_linear_interpolation():
    vals = list(range(1, 101))
    assert R.percentile(vals, 95) == pytest.approx(95.05)
    assert R.percentile([5.0], 95) == 5.0
    assert R.percentile([1, 2, 3, 4], 50) == 2.5
    with pytest.raises(ValueError):
        R.percentile([], 50)


def test_ttft_is_timed_from_due_and_counts_the_window_only():
    log = [rec(-1.0, -0.5, 4), rec(0.0, 0.3, 4, sent=0.1),
           rec(5.0, 5.2, 4), rec(10.0, 10.1, 4)]
    assert R.series(log, 10.0, "ttft_ms") == pytest.approx([300, 200])
    assert R.series(log, 10.0, "late_ms") == pytest.approx([100, 0])
    v, n = R.end_to_end({"series": "ttft_ms", "reduce": "percentile",
                         "q": 95}, log, 10.0, 0.0)
    assert n == 2 and v == pytest.approx(295.0)


def test_itl_over_burst_chunks():
    """A decodek burst of 4 tokens is 3 zero gaps and one long one."""
    log = [rec(0.0, 0.1, 12, burst=4, step=0.08)]
    gaps = R.series(log, 10.0, "itl_ms")
    assert len(gaps) == 11
    assert sorted(gaps)[:9] == [0.0] * 9
    assert sorted(gaps)[9:] == pytest.approx([80.0, 80.0])
    assert R.percentile(gaps, 95) == pytest.approx(80.0)
    assert R.percentile(gaps, 50) == 0.0


def test_tpot_is_per_request_over_tokens_minus_one():
    log = [rec(0.0, 0.1, 11, step=0.02), rec(1.0, 1.1, 21, step=0.04),
           rec(2.0, 2.1, 1)]
    assert R.series(log, 10.0, "tpot_ms") == pytest.approx([20.0, 40.0])
    v, n = R.end_to_end({"series": "tpot_ms", "reduce": "percentile",
                         "q": 50}, log, 10.0, 0.0)
    assert (v, n) == (pytest.approx(30.0), 2)


def test_pooled_tpot_is_over_all_tokens_not_over_requests():
    """100 tokens at 20 ms and 10 tokens at 200 ms: the median over
    requests is 110, the time per token over all of them 36.5."""
    log = [rec(0.0, 0.1, 101, step=0.02), rec(1.0, 1.1, 11, step=0.2)]
    spec = {"reduce": "pooled", "num": "decode_span_ms",
            "den": "decode_gaps"}
    v, n = R.end_to_end(spec, log, 10.0, 0.0)
    assert n == 2 and v == pytest.approx((2000 + 2000) / 110)
    assert R.end_to_end(spec, [], 10.0, 0.0) == (None, 0)
    assert R.end_to_end({"series": "ttft_ms", "reduce": "mean"}, log,
                        10.0, 0.0)[0] == pytest.approx(100.0)


def test_tokens_per_second_counts_what_streamed_inside_the_window():
    inside = rec(1.0, 1.5, 100, step=0.01)          # all 100 inside
    straddle = rec(-1.0, -0.5, 100, step=0.01)      # 50 before 0
    late = rec(9.0, 9.5, 100, step=0.01)            # 50 after 10
    never = {"due": 3.0, "sent": 3.0, "chunk_t": [], "end": 4.0}
    # served, but no visible text: its tokens count when it ended
    textless = {"due": 5.0, "sent": 5.0, "chunk_t": [], "end": 6.0,
                "completion_tokens": 7}
    log = [inside, straddle, late, never, textless]
    total = R.series(log, 10.0, "window_tokens")[0]
    assert total == pytest.approx(100 + 50 + 50 + 7, abs=1.5)
    v, _ = R.end_to_end({"series": "window_tokens",
                         "reduce": "per_second"}, log, 10.0, 0.0)
    assert v == pytest.approx(total / 10.0)


def test_usage_not_chunk_count_is_the_token_count():
    r = rec(0.0, 0.1, 10, step=0.01)
    r["completion_tokens"] = 20  # two tokens per chunk
    assert R.series([r], 10.0, "window_tokens") == [20.0]
    assert R.series([r], 10.0, "tpot_ms")[0] == pytest.approx(90 / 19)


def test_setup_and_empty_series():
    assert R.end_to_end({"reduce": "setup"}, [], 10.0, 61.5) == (61.5, 1)
    assert R.end_to_end({"series": "ttft_ms", "reduce": "percentile",
                         "q": 95}, [], 10.0, 0.0) == (None, 0)


E2E_FILES = sorted(
    f[:-5] for f in os.listdir(os.path.join(H.ROOT, "benchmark",
                                            "end_to_end")))


@pytest.mark.parametrize("name", E2E_FILES)
def test_every_end_to_end_file_reduces(name):
    with open(os.path.join(H.ROOT, "benchmark", "end_to_end",
                           name + ".json")) as f:
        spec = json.load(f)
    log = [rec(i * 0.5, i * 0.5 + 0.1, 16, burst=4) for i in range(10)]
    v, n = R.end_to_end(spec, log, 10.0, 42.0)
    assert v is not None and v > 0 and n >= 1


def test_histogram():
    assert R.histogram([1, 5, 50, 500], [10, 100]) == {
        "<=10": 2, "<=100": 1, ">100": 1}


# ---- /metrics -------------------------------------------------------------

BEFORE = """# HELP engine_requests_total Completed
# TYPE engine_requests_total counter
engine_requests_total{model="m",reason="length"} 10
engine_requests_total{model="m",reason="error"} 0
engine_queue_wait_seconds_bucket{model="m",le="0.01"} 10
engine_queue_wait_seconds_bucket{model="m",le="0.1"} 10
engine_queue_wait_seconds_bucket{model="m",le="+Inf"} 10
engine_queue_wait_seconds_sum{model="m"} 0.05
engine_queue_wait_seconds_count{model="m"} 10
engine_kv_pages_in_use_count{model="m"} 12
"""
AFTER = BEFORE.replace('reason="length"} 10', 'reason="length"} 110') \
    .replace('le="0.01"} 10', 'le="0.01"} 60') \
    .replace('le="0.1"} 10', 'le="0.1"} 100') \
    .replace('le="+Inf"} 10', 'le="+Inf"} 110') \
    .replace("in_use_count{model=\"m\"} 12", "in_use_count{model=\"m\"} 40")


def test_prom_parse_total_delta():
    b, a = prom.parse(BEFORE), prom.parse(AFTER)
    assert prom.total(b, "engine_requests_total") == 10
    assert prom.total(a, "engine_requests_total",
                      {"reason": "length"}) == 110
    assert prom.total(a, "engine_requests_total",
                      {"reason": ["error", "length"]}) == 110
    assert prom.delta(b, a, "engine_requests_total") == 100
    assert prom.total(a, "no_such_family") == 0


def test_prom_hist_quantile_is_of_the_window_only():
    b, a = prom.parse(BEFORE), prom.parse(AFTER)
    # 100 new observations: 50 <= 0.01, 40 in (0.01, 0.1], 10 beyond
    q50 = prom.hist_quantile(b, a, "engine_queue_wait_seconds", 0.5)
    assert q50 == pytest.approx(0.01)
    q80 = prom.hist_quantile(b, a, "engine_queue_wait_seconds", 0.8)
    assert q80 == pytest.approx(0.01 + 0.09 * 30 / 40)
    assert prom.hist_quantile(b, a, "engine_queue_wait_seconds", 0.95) \
        == pytest.approx(0.1)  # in the +Inf bucket: its lower edge
    assert prom.hist_quantile(b, b, "engine_queue_wait_seconds", 0.5) is None


def test_a_json_layer_metric_can_read_the_request_log(tmp_path):
    """A statistic of the harness's own request log, and a path of
    /backend/monitor with a scale, as data files."""
    (tmp_path / "hbm_peak_gb.json").write_text(json.dumps(
        {"source": "monitor", "path": "engine.hbm.peak_bytes_in_use",
         "scale": 1e-9}))
    mon = {"monitor": {"engine": {"hbm": {"peak_bytes_in_use": 12.5e9}}}}
    assert layer_metrics.evaluate(str(tmp_path), "hbm_peak_gb", None,
                                  mon) == pytest.approx(12.5)
    (tmp_path / "ttft_p50_ms.json").write_text(json.dumps(
        {"source": "requests", "series": "ttft_ms", "reduce": "percentile",
         "q": 50}))
    (tmp_path / "tpot_mean_ms.json").write_text(json.dumps(
        {"source": "requests", "series": "tpot_ms", "reduce": "mean"}))
    run = {"seconds": 10.0, "log": [rec(0.0, 0.25, 8), rec(1.0, 1.75, 8)]}
    ev = lambda n: layer_metrics.evaluate(str(tmp_path), n, None, run)  # noqa
    assert ev("ttft_p50_ms") == pytest.approx(500.0)
    assert ev("tpot_mean_ms") == pytest.approx(20.0)
    assert layer_metrics.evaluate(str(tmp_path), "ttft_p50_ms", None,
                                  {}) is None


def test_json_layer_metrics_read_counters_and_monitor():
    mdir = os.path.join(H.ROOT, "benchmark", "layer_metrics")
    run = {"metrics_before": prom.parse(BEFORE),
           "metrics_after": prom.parse(AFTER),
           "polls": [prom.parse(BEFORE), prom.parse(AFTER)],
           "monitor": {"load_s": 51.2,
                       "load_breakdown": {"warmup_s": 0.4},
                       "engine": {"hbm": {"peak_bytes_in_use": 12.5e9}}},
           "config": {"assumed": {"kv_pool_pages": 256}}}
    ev = lambda n: layer_metrics.evaluate(mdir, n, None, run)  # noqa: E731
    assert ev("load_s") == 51.2 and ev("warmup_s") == 0.4
    assert ev("queue_wait_p95_ms") == pytest.approx(100.0)
    assert ev("kv_pages_peak_share") == pytest.approx(100 * 40 / 256)
    assert ev("decode_rows_mean") is None  # nothing to read: left out
    run["metrics_after"] = prom.parse(
        AFTER + 'engine_ragged_rows_total{kind="decode"} 90\n'
        'engine_mixed_dispatch_total{composition="mixed"} 4\n'
        'engine_mixed_dispatch_total{composition="decode_only"} 6\n'
        'engine_mixed_dispatch_total{composition="prefill_only"} 50\n')
    assert ev("decode_rows_mean") == 9.0
    assert layer_metrics.evaluate(mdir, "load_s", None, {}) is None
    with pytest.raises(FileNotFoundError):
        layer_metrics.evaluate(mdir, "no_such_metric", None, run)
