import math

import pytest

from benchmark import stats


@pytest.mark.parametrize("values,q,want", [
    ([5, 1, 3, 2, 4], 50, 3), ([5, 1, 3, 2, 4], 90, 5), ([5, 1, 3, 2, 4], 20, 1),
    (list(range(1, 101)), 90, 90), (list(range(1, 136)), 90, 122), ([7], 90, 7),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_tpot_is_the_requests_mean_gap():
    assert stats.tpot_ms(1.0, 1.9, 10) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def _rec(ok=True, ttft=100.0, tpot=20.0, latency=500.0):
    return {"ok": ok, "ttft_ms": ttft, "tpot_ms": tpot, "latency_ms": latency}


def test_slo_share_counts_failures_as_misses():
    recs = [_rec()] * 6 + [_rec(ttft=2000.0), _rec(tpot=80.0),
                           _rec(ok=False, ttft=None, tpot=None),
                           _rec(tpot=None)]
    out = stats.end_to_end(recs, 10.0, {"ttft_ms": 1000.0, "tpot_ms": 60.0},
                           tokens_in_window=500, censor_ms=40000.0)
    assert out["slo_share"] == pytest.approx(70.0)    # 6 + the one-token answer
    assert out["out_tok_per_s"] == pytest.approx(50.0)
    assert out["ttft_p90_ms"] == 2000.0               # the failure is the max
    assert stats.percentile([r["ttft_ms"] if r["ok"] else 40000.0
                             for r in recs], 100) == 40000.0


def test_a_failed_request_stays_in_both_tails():
    recs = [_rec()] * 4 + [_rec(ok=False, ttft=None, tpot=None)]
    out = stats.end_to_end(recs, 1.0, {"ttft_ms": 1000.0, "tpot_ms": 60.0},
                           0, censor_ms=9000.0)
    assert out["ttft_p90_ms"] == 9000.0 and out["tpot_p90_ms"] == 9000.0
    assert out["ttft_p50_ms"] == 100.0 and out["tpot_p50_ms"] == 20.0
    assert out["ttft_mean_ms"] == pytest.approx((4 * 100.0 + 9000.0) / 5)
    assert out["latency_mean_ms"] == pytest.approx((4 * 500.0 + 9000.0) / 5)
    assert out["slo_share"] == pytest.approx(80.0)


def test_lateness():
    late = stats.lateness_ms([{"due": 1.0, "sent": 1.001},
                              {"due": 2.0, "sent": 2.003},
                              {"due": 3.0, "sent": 3.010}])
    assert late["p50"] == pytest.approx(3.0) and late["max"] == pytest.approx(10.0)


EXPO = '''# HELP kukeon_x_seconds h
# TYPE kukeon_x_seconds histogram
kukeon_x_seconds_bucket{le="0.1"} %d
kukeon_x_seconds_bucket{le="1"} %d
kukeon_x_seconds_bucket{le="+Inf"} %d
kukeon_x_seconds_count %d
kukeon_engine_prefix_cache_total{result="hit"} %d
kukeon_engine_prefix_cache_total{result="miss"} %d
kukeon_compiles_total{program="prefill"} 3
kukeon_compiles_total{program="decode"} 4
'''


def test_prometheus_parse_delta_and_histogram_quantile():
    a = stats.parse_prometheus(EXPO % (10, 10, 10, 10, 1, 1))
    b = stats.parse_prometheus(EXPO % (60, 100, 110, 110, 31, 11))
    assert stats.sample(a, "kukeon_compiles_total") == 7
    assert stats.sample(a, "kukeon_compiles_total", program="decode") == 4
    assert stats.delta(a, b, "kukeon_engine_prefix_cache_total",
                       result="hit") == 30
    # window: 50 under 0.1 s, 40 more under 1 s, 10 beyond: p90 is the 1 s edge
    assert stats.histogram_quantile(a, b, "kukeon_x_seconds", 90) == pytest.approx(1.0)
    assert stats.histogram_quantile(a, b, "kukeon_x_seconds", 25) == pytest.approx(0.05)
    assert stats.histogram_quantile(a, a, "kukeon_x_seconds", 90) is None
    assert stats.sample(a, "kukeon_absent_total") == 0


def test_mean_live_rows_and_slots():
    rec = {"prompt_len": 100, "token_times": [1.0 + 0.1 * i for i in range(11)]}
    live = stats.mean_live([rec], 1.0, 2.0)
    assert live["slots"] == pytest.approx(1.0)
    assert 100 < live["kv_rows"] < 111
    assert stats.mean_live([rec], 5.0, 6.0) == {"slots": 0.0, "kv_rows": 0.0}
    assert math.isclose(stats.mean_live([rec], 1.5, 2.5)["slots"], 0.5)
