"""The in-daemon time-series store (obs/tsdb.py): selector/window parsing,
counter-reset-aware rates, histogram-aware windowed percentiles against
exact values, retention + series-cap bounds under flood, a sanitizer-armed
concurrent ingest/query hammer."""

from __future__ import annotations

import threading
import time

import pytest

from kukeon_tpu.obs import Registry, expo, percentile_from_counts
from kukeon_tpu.obs import federate as fed
from kukeon_tpu.obs.tsdb import (
    TSDB,
    parse_expr,
    parse_selector,
    parse_window,
    sparkline,
)


def _fam(name: str, kind: str, *samples) -> dict:
    """families dict with one family; samples are (labels, value) pairs
    (sample name == family name — counters/gauges)."""
    return {name: fed.Family(name, kind, "", [
        (name, dict(labels), str(value)) for labels, value in samples])}


# --- parsing -----------------------------------------------------------------


def test_parse_window_units():
    assert parse_window("30s") == 30.0
    assert parse_window("5m") == 300.0
    assert parse_window("1h") == 3600.0
    assert parse_window("250ms") == 0.25
    assert parse_window(300) == 300.0
    assert parse_window("300") == 300.0
    for bad in ("", "abc", "5x", "-3s", 0, -1):
        with pytest.raises(ValueError):
            parse_window(bad)


def test_parse_selector_label_forms():
    s = parse_selector('kukeon_x{a=1,b="two words",c=v}')
    assert s.family == "kukeon_x"
    assert dict(s.matchers) == {"a": "1", "b": "two words", "c": "v"}
    assert parse_selector("kukeon_x").matchers == ()
    for bad in ("", "{a=1}", "kukeon_x{a}", "kukeon_x{a=1", "1bad"):
        with pytest.raises(ValueError):
            parse_selector(bad)


def test_parse_expr_ratio():
    left, right = parse_expr("kukeon_a{x=1} / kukeon_b{x=1}")
    assert left.family == "kukeon_a" and right.family == "kukeon_b"
    left, right = parse_expr("kukeon_a")
    assert right is None
    with pytest.raises(ValueError):
        parse_expr("a / b / c")


# --- counters and resets -----------------------------------------------------


def test_counter_rate_handles_reset():
    """A cell restart drops its cumulative counters to ~0 mid-window; the
    increase must treat the post-reset value as growth since the reset,
    never as a negative delta."""
    db = TSDB(retention_s=3600, clock=lambda: 0)
    for at, v in ((0, 10), (10, 20), (20, 30), (30, 4), (40, 9)):
        db.ingest(_fam("kukeon_c_total", "counter", ({}, v)), at=at)
    # increases: 10 + 10 + 4 (reset: post-reset value) + 5 = 29
    [(labels, delta)] = db.query("kukeon_c_total", 100, "delta", at=40)
    assert delta == 29.0
    [(_l, rate)] = db.query("kukeon_c_total", 100, "rate", at=40)
    assert rate == pytest.approx(0.29)
    # Without the reset the same window reads last-baseline correctly.
    [(_l, d2)] = db.query("kukeon_c_total", 25, "delta", at=20)
    assert d2 == 20.0   # baseline point at t=0 + window (0, 20]


def test_gauge_window_aggregations():
    db = TSDB(retention_s=3600, clock=lambda: 0)
    for at, v in ((0, 5), (10, 1), (20, 9), (30, 3)):
        db.ingest(_fam("kukeon_g", "gauge", ({"cell": "a"}, v)), at=at)
    q = lambda agg, w=100, at=30: db.query("kukeon_g", w, agg, at=at)
    assert q("avg") == [({"cell": "a"}, 4.5)]
    assert q("max") == [({"cell": "a"}, 9.0)]
    assert q("min") == [({"cell": "a"}, 1.0)]
    assert q("latest") == [({"cell": "a"}, 3.0)]
    # Gauge delta is signed last-minus-first (no reset detection).
    assert q("delta", w=25) == [({"cell": "a"}, -2.0)]
    # No points inside the window -> series omitted, not a zero.
    assert q("avg", w=5, at=100) == []
    with pytest.raises(ValueError):
        q("median")


# --- histograms --------------------------------------------------------------


def _hist_families(h_reg: Registry) -> dict:
    return fed.parse(expo.render(h_reg))


def test_windowed_percentile_matches_exact():
    """Full-window percentile over ingested scrapes equals the live
    histogram's own estimate (same buckets, same interpolation)."""
    reg = Registry()
    h = reg.histogram("kukeon_t_seconds", "t")
    db = TSDB(retention_s=3600, clock=lambda: 0)
    # Baseline scrape before any traffic: a counter's first-ever sample
    # is a baseline, not an in-window increase (a daemon restarting next
    # to mid-life cells must not read their lifetime totals as fresh).
    db.ingest(_hist_families(reg), at=5)
    values = (0.001, 0.004, 0.004, 0.02, 0.09, 0.3, 1.7)
    for i, v in enumerate(values):
        h.observe(v)
        db.ingest(_hist_families(reg), at=10 * (i + 1))
    for q, agg in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
        [(labels, est)] = db.query("kukeon_t_seconds", 1000, agg, at=80)
        assert labels == {}
        assert est == pytest.approx(h.percentile(q))


def test_windowed_percentile_is_a_window_delta():
    """Only in-window bucket growth counts: a flood of fast observations
    before the window must not drag the windowed p95 down."""
    reg = Registry()
    h = reg.histogram("kukeon_t_seconds", "t")
    db = TSDB(retention_s=3600, clock=lambda: 0)
    for _ in range(500):
        h.observe(0.001)                      # ancient, outside the window
    db.ingest(_hist_families(reg), at=10)
    slow = (0.5, 0.6, 0.9, 1.3)
    for v in slow:
        h.observe(v)
    db.ingest(_hist_families(reg), at=100)
    [(_l, est)] = db.query("kukeon_t_seconds", 95, "p95", at=100)
    # Expected: the p95 of JUST the slow delta, bucket-estimated.
    counts = [0] * (len(h.buckets) + 1)
    for v in slow:
        for i, b in enumerate(h.buckets):
            if v <= b:
                counts[i] += 1
                break
    want = percentile_from_counts(h.buckets, counts, 0.95)
    assert est == pytest.approx(want)
    # Sanity: the since-boot estimate is far lower (fast flood dominates).
    assert h.percentile(0.95) < 0.01 < est


def test_histogram_reset_mid_window_stays_sane():
    """Cell restart: cumulative bucket counters drop to a fresh process's
    small values. Windowed percentiles must clamp, not go negative or
    raise."""
    reg = Registry()
    h = reg.histogram("kukeon_t_seconds", "t")
    db = TSDB(retention_s=3600, clock=lambda: 0)
    for _ in range(50):
        h.observe(0.004)
    db.ingest(_hist_families(reg), at=10)
    reg2 = Registry()                          # the restarted cell
    h2 = reg2.histogram("kukeon_t_seconds", "t")
    for _ in range(3):
        h2.observe(0.03)
    db.ingest(_hist_families(reg2), at=20)
    [(_l, est)] = db.query("kukeon_t_seconds", 100, "p95", at=20)
    assert 0 < est <= h.buckets[-1]
    # Post-reset observations count as the increase: p95 lands near the
    # restarted cell's 0.03 bucket, not the dead process's 0.004.
    assert est >= 0.01


def test_ratio_query_label_join():
    db = TSDB(retention_s=3600, clock=lambda: 0)
    db.ingest(_fam("kukeon_hbm_bytes_in_use", "gauge",
                   ({"cell": "a", "device": "0"}, 90),
                   ({"cell": "b", "device": "0"}, 10)), at=10)
    db.ingest(_fam("kukeon_hbm_bytes_limit", "gauge",
                   ({"cell": "a", "device": "0"}, 100),
                   ({"cell": "b", "device": "0"}, 100)), at=10)
    res = dict((labels["cell"], v) for labels, v in db.query(
        "kukeon_hbm_bytes_in_use / kukeon_hbm_bytes_limit",
        60, "max", at=10))
    assert res == {"a": pytest.approx(0.9), "b": pytest.approx(0.1)}


# --- bounds ------------------------------------------------------------------


def test_retention_eviction_under_flood():
    db = TSDB(retention_s=100, clock=lambda: 0)
    for i in range(500):
        db.ingest(_fam("kukeon_g", "gauge", ({"cell": "a"}, i)), at=i * 10)
    st = db.stats()
    assert st["series"] == 1
    # 100s retention at 10s cadence: ~10 live points, never 500.
    assert st["points"] <= 12
    assert db.query("kukeon_g", 100, "latest", at=4990) == [
        ({"cell": "a"}, 499.0)]
    # A series that stops updating is GC'd after a full retention window.
    db.ingest(_fam("kukeon_other", "gauge", ({}, 1)), at=5000)
    for i in range(30):
        db.ingest(_fam("kukeon_g", "gauge", ({"cell": "a"}, i)),
                  at=5000 + (i + 1) * 10)
    assert ("kukeon_other" not in
            {name for (name, _k) in db._series.keys()})


def test_series_cap_drops_and_counts():
    db = TSDB(retention_s=100, max_series=5, clock=lambda: 0)
    for i in range(10):
        db.ingest(_fam("kukeon_g", "gauge", ({"cell": str(i)}, 1)), at=1)
    st = db.stats()
    assert st["series"] == 5
    assert st["droppedSeries"] == 5


# --- ranges, sparklines, exemplars -------------------------------------------


def test_query_range_and_sparkline():
    db = TSDB(retention_s=3600, clock=lambda: 0)
    for i in range(10):
        db.ingest(_fam("kukeon_c_total", "counter", ({"cell": "a"}, i * 6)),
                  at=i * 10)
    [(labels, vals)] = db.query_range("kukeon_c_total", 60, 20, "rate",
                                      at=90)
    assert labels == {"cell": "a"}
    assert len(vals) == 3
    assert all(v == pytest.approx(0.6) for v in vals)
    # Sparkline: gaps render as spaces, values as blocks.
    line = sparkline([1.0, None, 8.0, 4.0])
    assert len(line) == 4 and line[1] == " " and line[0] != " "


def test_latest_exemplar_roundtrip():
    reg = Registry()
    h = reg.histogram("kukeon_t_seconds", "t")
    h.observe(0.02, exemplar="ab" * 16)
    db = TSDB(retention_s=3600, clock=lambda: 0)
    fams = _hist_families(reg)
    fed.inject_label(fams, cell="r/s/st/c")
    db.ingest(fams, at=10)
    got = db.latest_exemplar("kukeon_t_seconds", cell="r/s/st/c")
    assert got is not None and got[0] == "ab" * 16
    assert db.latest_exemplar("kukeon_t_seconds", cell="nope") is None


# --- concurrency -------------------------------------------------------------


def test_concurrent_ingest_query_hammer():
    """Ingest/query/stats from many threads at once; under a
    KUKEON_SANITIZE=1 session the conftest gate also fails this test on
    any lock-discipline finding (the tsdb builds rows outside its lock)."""
    db = TSDB(retention_s=50, max_series=256)
    reg = Registry()
    h = reg.histogram("kukeon_t_seconds", "t")
    for v in (0.001, 0.02, 0.3):
        h.observe(v)
    base_fams = expo.render(reg)
    stop = threading.Event()
    errors: list[BaseException] = []

    def ingester(i: int):
        n = 0
        while not stop.is_set():
            fams = fed.parse(base_fams)
            fed.inject_label(fams, cell=f"cell-{i}")
            db.ingest(fams, at=time.time() + n)
            n += 1

    def querier():
        while not stop.is_set():
            db.query("kukeon_t_seconds", 30, "p95")
            db.query("kukeon_t_seconds_count", 30, "rate")
            db.query_range("kukeon_t_seconds_count", 30, 10, "delta")
            db.stats()

    def run(fn, *a):
        def wrapped():
            try:
                fn(*a)
            except BaseException as e:  # noqa: BLE001 — surface to the main thread
                errors.append(e)
        t = threading.Thread(target=wrapped, daemon=True)
        t.start()
        return t

    threads = [run(ingester, i) for i in range(4)] + [
        run(querier) for _ in range(4)]
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors, errors
    st = db.stats()
    assert st["series"] > 0 and st["ingests"] > 0
