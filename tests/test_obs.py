"""Observability layer (kukeon_tpu/obs): registry semantics, Prometheus
exposition golden format, trace-span lifecycle (including the PR-2 shed and
deadline-expiry paths), cell /metrics + /v1/trace endpoints under load, and
the fault-point/counter guard."""

from __future__ import annotations

import http.client
import json
import os
import re
import threading
import time
from http.server import ThreadingHTTPServer

import jax
import numpy as np
import pytest

from kukeon_tpu import faults
from kukeon_tpu.models import llama
from kukeon_tpu.obs import (
    LATENCY_BUCKETS_S,
    Registry,
    Tracer,
    expo,
    render,
)
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import RejectedError, SamplingParams, ServingEngine

PROMPT = np.arange(1, 9, dtype=np.int32)


def _tiny_engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 1)
    return ServingEngine(cfg, params, mesh, max_seq_len=96,
                        decode_chunk=4, **kw)


# --- registry semantics ------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("kukeon_t_total", "help", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3
    assert c.value(kind="b") == 1
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")            # counters only go up
    g = reg.gauge("kukeon_t_gauge", "g")
    g.set(5)
    g.dec(2)
    assert g.value() == 3
    g.set_function(lambda: 42)
    assert g.value() == 42             # callable wins over stored value
    h = reg.histogram("kukeon_t_seconds", "h")
    h.observe(0.001)
    counts, total, n = h.snapshot()
    assert n == 1 and abs(total - 0.001) < 1e-9
    assert sum(counts) == 1


def test_registry_get_or_create_is_idempotent_and_typed():
    reg = Registry()
    a = reg.counter("kukeon_same_total", "x")
    b = reg.counter("kukeon_same_total", "different help ignored")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("kukeon_same_total", "now a gauge?")
    with pytest.raises(ValueError):
        reg.counter("kukeon_same_total", "x", labels=("k",))


def test_histogram_percentiles():
    reg = Registry()
    h = reg.histogram("kukeon_p_seconds", "p")
    assert h.percentile(0.5) is None   # no observations yet
    for v in (0.001, 0.002, 0.004, 0.008, 0.016, 0.032):
        h.observe(v)
    p50 = h.percentile(0.5)
    assert 0.001 <= p50 <= 0.008
    # Overflow clamps to the top finite bound rather than inventing data.
    h.observe(10_000.0)
    assert h.percentile(1.0) == h.buckets[-1]
    assert LATENCY_BUCKETS_S[0] <= 0.001   # ladder reaches ITL scale


def test_registry_hammer_counts_are_exact():
    """Multi-threaded registry hammer: no torn reads, no lost increments —
    counters and histogram counts land exactly."""
    reg = Registry()
    c = reg.counter("kukeon_hammer_total", "h", labels=("t",))
    h = reg.histogram("kukeon_hammer_seconds", "h")
    g = reg.gauge("kukeon_hammer_gauge", "h")
    N_THREADS, N_ITER = 8, 2000

    def worker(tid: int):
        for i in range(N_ITER):
            c.inc(t=str(tid % 2))
            h.observe(0.0001 * (i % 50))
            g.inc()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert c.value(t="0") + c.value(t="1") == N_THREADS * N_ITER
    counts, _total, n = h.snapshot()
    assert n == N_THREADS * N_ITER
    assert sum(counts) == n
    assert g.value() == N_THREADS * N_ITER


# --- exposition golden format ------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"(?:,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})?'
    r' (-?(?:\d+\.?\d*(?:e-?\d+)?|\+Inf|-Inf|NaN))$'
)


def _parse_expo(text: str) -> dict[str, dict]:
    """Strict parser for the subset of the Prometheus text format expo.py
    emits: families {name: {"type", "help", "samples": [(labels, value)]}}.
    Raises on any malformed line — this IS the golden assertion."""
    families: dict[str, dict] = {}
    declared: str | None = None
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if line.startswith("# HELP "):
            name = line.split()[2]
            families.setdefault(name, {"samples": []})["help"] = line
            declared = name
        elif line.startswith("# EXEMPLAR "):
            # Histogram trace exemplars ride as comment lines (any 0.0.4
            # scraper ignores them); the golden parser pins their syntax.
            m = re.match(
                r'^# EXEMPLAR ([a-zA-Z_:][a-zA-Z0-9_:]*_bucket)(\{.*\})? '
                r'trace_id="[0-9a-fA-F]*" value=\S+$', line)
            assert m, f"malformed exemplar line: {line!r}"
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            assert name == declared, f"TYPE without preceding HELP: {line}"
            assert kind in ("counter", "gauge", "histogram"), line
            families[name]["type"] = kind
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample line: {line!r}"
            name = m.group(1)
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            fam = families.get(name) or families.get(base)
            assert fam is not None, f"sample before family declaration: {line}"
            labels = {}
            if m.group(2):
                for kv in re.findall(r'([a-zA-Z0-9_]+)="((?:[^"\\]|\\.)*)"',
                                     m.group(2)):
                    labels[kv[0]] = kv[1]
            fam["samples"].append((name, labels, m.group(3)))
    return families


def test_exposition_golden_format():
    reg = Registry()
    c = reg.counter("kukeon_g_total", "a counter", labels=("kind",))
    c.inc(kind='weird "value"\nwith escapes')
    reg.gauge("kukeon_g_gauge", "a gauge").set(1.5)
    h = reg.histogram("kukeon_g_seconds", "a histogram")
    for v in (0.0001, 0.01, 1.0, 500.0):
        h.observe(v)
    text = render(reg)
    families = _parse_expo(text)
    assert families["kukeon_g_total"]["type"] == "counter"
    assert families["kukeon_g_gauge"]["type"] == "gauge"
    assert families["kukeon_g_seconds"]["type"] == "histogram"
    # Label values survive escaping and round-trip through the parser.
    (_n, labels, v), = families["kukeon_g_total"]["samples"]
    assert labels["kind"] == 'weird \\"value\\"\\nwith escapes'
    assert v == "1"
    # Histogram invariants: cumulative bucket counts are monotone, the
    # +Inf bucket equals _count, and _sum matches the observations.
    hs = families["kukeon_g_seconds"]["samples"]
    buckets = [(lab["le"], float(val)) for n, lab, val in hs
               if n.endswith("_bucket")]
    assert buckets[-1][0] == "+Inf"
    values = [v for _le, v in buckets]
    assert values == sorted(values), "bucket counts must be cumulative"
    count = next(float(v) for n, _l, v in hs if n.endswith("_count"))
    total = next(float(v) for n, _l, v in hs if n.endswith("_sum"))
    assert values[-1] == count == 4
    assert abs(total - 501.0101) < 1e-6
    # le bounds are strictly increasing (bucket monotonicity by bound too).
    finite = [float(le) for le, _v in buckets[:-1]]
    assert finite == sorted(finite) and len(set(finite)) == len(finite)


def test_collector_families_render():
    reg = Registry()
    reg.register_collector(lambda: iter([
        ("kukeon_extra_total", "counter", "from a collector",
         [({"k": "v"}, 3.0)]),
    ]))
    text = render(reg)
    fams = _parse_expo(text)
    assert ("kukeon_extra_total", {"k": "v"}, "3") in \
        fams["kukeon_extra_total"]["samples"]


# --- trace spans -------------------------------------------------------------


def test_tracer_ring_buffer_bounded():
    t = Tracer(capacity=3)
    for i in range(10):
        t.finish(t.begin(i, 1), "ok")
    spans = t.recent(100)
    assert len(spans) == 3
    assert [s["requestId"] for s in spans] == [9, 8, 7]   # newest first


def test_span_phases_partition_e2e():
    t = Tracer()
    s = t.begin(7, 16)
    s.event("admitted")
    time.sleep(0.01)
    s.event("prefill_dispatched")
    s.event("first_token")
    time.sleep(0.005)
    t.finish(s, "ok", tokens=3)
    d = t.recent(1)[0]
    assert d["outcome"] == "ok" and d["tokens"] == 3
    assert set(d["phasesS"]) == {"queued", "prefill_dispatch",
                                 "prefill_wait", "decode"}
    assert abs(sum(d["phasesS"].values()) - d["e2eS"]) < 1e-3


def test_engine_trace_lifecycle_ok_path():
    eng = _tiny_engine()
    got = eng.generate(PROMPT, SamplingParams(max_new_tokens=6))
    assert len(got) == 6
    span = eng.tracer.recent(1)[0]
    assert span["outcome"] == "ok"
    assert span["tokens"] == 6
    assert span["promptTokens"] == PROMPT.size
    assert span["decodeChunks"] >= 1
    events = [e["event"] for e in span["events"]]
    assert events == ["submitted", "admitted", "prefill_dispatched",
                      "first_token", "finished"]
    # Acceptance: phase durations sum (within tolerance) to e2e latency.
    assert abs(sum(span["phasesS"].values()) - span["e2eS"]) < 1e-3


def test_engine_trace_shed_path():
    """The PR-2 admission-shed path records both the counter and a span."""
    eng = _tiny_engine(max_pending=1)
    held = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    with pytest.raises(RejectedError):
        eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    assert eng.shed_stats["rejected"] == 1
    assert eng._m_requests.value(outcome="shed") == 1
    span = eng.tracer.recent(1)[0]
    assert span["outcome"] == "shed"
    assert span["requestId"] == -1     # never admitted, never got an id
    assert span["tokens"] == 0
    held.cancel()
    while not held.done.is_set():
        eng.step()


def test_engine_trace_deadline_expiry_paths():
    """Deadline expiry while QUEUED and while ACTIVE both finish their
    spans with outcome=timeout, and the phases still partition e2e."""
    eng = _tiny_engine()
    hog = eng.submit(PROMPT, SamplingParams(max_new_tokens=64))
    eng.step()                          # hog takes THE slot
    queued_victim = eng.submit(PROMPT, SamplingParams(max_new_tokens=4),
                               deadline_s=0.01)
    time.sleep(0.03)
    eng.step()
    assert queued_victim.timed_out
    span = eng.tracer.recent(1)[0]
    assert span["outcome"] == "timeout"
    assert span["requestId"] == queued_victim.id
    assert list(span["phasesS"]) == ["queued"]   # never left the queue
    assert abs(sum(span["phasesS"].values()) - span["e2eS"]) < 1e-3
    assert eng._m_requests.value(outcome="timeout") == 1

    hog.cancel()
    while not hog.done.is_set():
        eng.step()
    active_victim = eng.submit(PROMPT, SamplingParams(max_new_tokens=500),
                               deadline_s=0.3)
    while not active_victim.done.is_set():
        eng.step()
    assert active_victim.timed_out
    span = next(s for s in eng.tracer.recent(4)
                if s["requestId"] == active_victim.id)
    assert span["outcome"] == "timeout"
    assert span["decodeChunks"] >= 1 and span["tokens"] >= 1
    assert "decode" in span["phasesS"]
    assert abs(sum(span["phasesS"].values()) - span["e2eS"]) < 1e-3
    assert eng.shed_stats["timed_out"] == 2
    # The cancelled hog got its own terminal span too.
    assert eng._m_requests.value(outcome="cancelled") == 1


def test_engine_metrics_families_after_traffic():
    eng = _tiny_engine(max_pending=4)
    eng.generate(PROMPT, SamplingParams(max_new_tokens=5))
    text = render(eng.registry)
    fams = _parse_expo(text)
    for name, kind in (
        ("kukeon_engine_queue_wait_seconds", "histogram"),
        ("kukeon_engine_prefill_tokens_total", "counter"),
        ("kukeon_engine_loop_seconds_total", "counter"),
        ("kukeon_engine_steps_total", "counter"),
        ("kukeon_engine_ttft_seconds", "histogram"),
        ("kukeon_engine_inter_token_seconds", "histogram"),
        ("kukeon_engine_e2e_seconds", "histogram"),
        ("kukeon_engine_tokens_total", "counter"),
        ("kukeon_engine_requests_total", "counter"),
        ("kukeon_engine_shed_total", "counter"),
        ("kukeon_engine_slots_total", "gauge"),
        ("kukeon_engine_slots_free", "gauge"),
        ("kukeon_engine_queue_depth", "gauge"),
        ("kukeon_engine_max_pending", "gauge"),
        ("kukeon_engine_host_sync_total", "counter"),
        ("kukeon_engine_decode_chunks_total", "counter"),
        ("kukeon_faults_fired_total", "counter"),
    ):
        assert fams.get(name, {}).get("type") == kind, name
    # Prefill tokens by kind; the 8-token prompt padded to the 64 bucket.
    pre = {lab["kind"]: float(v) for _n, lab, v
           in fams["kukeon_engine_prefill_tokens_total"]["samples"]}
    assert pre == {"real": len(PROMPT), "padded": 64.0, "cached": 0.0}
    # Transfer counters mirror the sync_stats seam exactly.
    hs = {lab["kind"]: float(v)
          for n, lab, v in fams["kukeon_engine_host_sync_total"]["samples"]}
    assert hs["fetch"] == eng.sync_stats["fetches"]
    assert hs["upload"] == eng.sync_stats["uploads"]


# --- fault-point guard -------------------------------------------------------


def test_every_fault_point_call_site_is_declared():
    """Guard (conftest-level contract): every ``maybe_fail("<point>")``
    call site in the package appears in faults.POINTS, and every declared
    point has a call site — a new fault point can't ship unobservable,
    and a stale declaration can't linger after a seam is removed.

    Since PR 7 this rides kukelint's AST-accurate KUKE007 registry pass
    (kukeon_tpu/analysis/registries.py) instead of a regex over source
    text: dynamic point names are themselves a violation, and failures
    carry file:line."""
    from kukeon_tpu.analysis import load_sources, run_analysis
    from kukeon_tpu.analysis.registries import collect_fault_call_sites

    pkg_root = os.path.dirname(os.path.abspath(faults.__file__))
    findings = run_analysis(pkg_root, select=["KUKE007"])
    assert findings == [], "\n".join(f.render() for f in findings)
    # Vacuity guard: the pass really saw the package's call sites (a scan
    # rooted in the wrong directory would pass trivially).
    sites = {p for _f, p, _l in collect_fault_call_sites(
        load_sources(pkg_root))}
    assert sites == set(faults.POINTS)


@pytest.mark.faults
def test_every_fault_point_has_a_fired_counter():
    """Every declared point exposes kukeon_faults_fired_total{point=...}
    (zero unfired), and a fired point's count lands on the scrape."""
    reg = Registry()
    reg.register_collector(expo.faults_collector)
    fams = _parse_expo(render(reg))
    seen = {lab["point"]: float(v) for _n, lab, v
            in fams["kukeon_faults_fired_total"]["samples"]}
    assert set(faults.POINTS) <= set(seen)
    assert all(v == 0 for v in seen.values())
    os.environ[faults.ENV] = "engine.decode:1:2"
    for _ in range(2):
        with pytest.raises(faults.FaultInjected):
            faults.maybe_fail("engine.decode")
    fams = _parse_expo(render(reg))
    seen = {lab["point"]: float(v) for _n, lab, v
            in fams["kukeon_faults_fired_total"]["samples"]}
    assert seen["engine.decode"] == 2


# --- cell endpoints under load (tier-1 acceptance) ---------------------------


@pytest.fixture(scope="module")
def obs_cell():
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    cell = ServingCell("tiny", num_slots=2, max_seq_len=96, checkpoint=None,
                       dtype=None, max_pending=8)
    cell.engine.start()
    cell.mark_ready()
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield cell, server.server_address[1]
    server.shutdown()
    server.server_close()
    cell.engine.stop()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    raw = resp.read()
    ctype = resp.getheader("Content-Type")
    conn.close()
    return resp.status, raw, ctype


def test_metrics_scrape_is_valid_while_flooded(obs_cell):
    """Acceptance: /metrics parses as Prometheus text — with the required
    histogram/counter/gauge families — WHILE a flood of requests is in
    flight, and /v1/trace spans' phases sum to their e2e latency."""
    cell, port = obs_cell
    eng = cell.engine
    sp = SamplingParams(max_new_tokens=3)
    flood: list = []
    rejected = 0
    for _ in range(24):
        try:
            flood.append(eng.submit(PROMPT, sp))
        except RejectedError:
            rejected += 1
    # Scrape repeatedly mid-flight: every scrape must parse cleanly.
    for _ in range(5):
        status, raw, ctype = _get(port, "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        fams = _parse_expo(raw.decode())
        for name in ("kukeon_engine_ttft_seconds",
                     "kukeon_engine_inter_token_seconds",
                     "kukeon_engine_e2e_seconds",
                     "kukeon_engine_queue_wait_seconds",
                     "kukeon_engine_prefill_tokens_total",
                     "kukeon_engine_loop_seconds_total",
                     "kukeon_engine_shed_total",
                     "kukeon_engine_slots_free",
                     "kukeon_engine_queue_depth",
                     "kukeon_watchdog_probes_total",
                     "kukeon_watchdog_trips_total",
                     "kukeon_faults_fired_total",
                     "kukeon_cell_ready",
                     "kukeon_cell_uptime_seconds"):
            assert name in fams, name
    deadline = time.monotonic() + 120
    for r in flood:
        assert r.done.wait(timeout=max(0.0, deadline - time.monotonic()))
    # Settle: the terminal emit races the span append by design.
    deadline = time.monotonic() + 10
    while len(eng.tracer) < len(flood) and time.monotonic() < deadline:
        time.sleep(0.02)
    status, raw, _ = _get(port, f"/v1/trace?n={len(flood) + 8}")
    assert status == 200
    spans = json.loads(raw)["spans"]
    ok_spans = [s for s in spans if s["outcome"] == "ok"]
    assert len(ok_spans) >= len(flood)
    for s in ok_spans:
        assert abs(sum(s["phasesS"].values()) - s["e2eS"]) < 1e-3
    # The scrape agrees with the JSON stats view (same registry).
    status, raw, _ = _get(port, "/v1/stats")
    stats = json.loads(raw)
    fams = _parse_expo(_get(port, "/metrics")[1].decode())
    shed = {lab["reason"]: float(v) for _n, lab, v
            in fams["kukeon_engine_shed_total"]["samples"]}
    assert shed.get("rejected", 0) == stats["rejected"] == rejected


def test_trace_endpoint_bounds_and_validates(obs_cell):
    _cell, port = obs_cell
    status, raw, _ = _get(port, "/v1/trace?n=1")
    assert status == 200
    assert len(json.loads(raw)["spans"]) <= 1
    status, _raw, _ = _get(port, "/v1/trace?n=bogus")
    assert status == 400


def test_watchdog_counters_land_on_registry():
    from kukeon_tpu.runtime.serving_cell import EngineWatchdog

    class _Stalled:
        last_progress = 0.0

        def stalled_s(self):
            return 1e9

    reg = Registry()
    wd = EngineWatchdog(_Stalled(), stall_budget_s=0.01, interval_s=0.01,
                        probe=lambda timeout_s: ("wedged", "injected"),
                        on_wedged=lambda d: None, registry=reg)
    wd.start()
    wd.join(timeout=10)
    assert wd.tripped
    assert reg.get("kukeon_watchdog_trips_total").value() == 1
    assert reg.get("kukeon_watchdog_probes_total").value(verdict="wedged") == 1


def test_embedding_cell_stats_parity():
    """EmbeddingCell.stats() reports the same ready/draining/uptime fields
    the decoder cell does, so scrapers treat both flavors uniformly."""
    from kukeon_tpu.runtime.serving_cell import EmbeddingCell, ServingCell

    ec = EmbeddingCell("bge-tiny", batch_size=4)
    dc = ServingCell("tiny", num_slots=1, max_seq_len=96, checkpoint=None,
                     dtype=None)
    try:
        for key in ("ready", "draining", "uptimeSeconds", "unreadyReason",
                    "devices", "platform", "deviceKind"):
            assert key in ec.stats(), key
            assert key in dc.stats(), key
        # The device census is what JAX reports in the process that holds
        # the device (chip_smoke.py's last line is read off these fields).
        d0 = jax.devices()[0]
        for s in (ec.stats(), dc.stats()):
            assert s["platform"] == d0.platform == "cpu"
            assert s["deviceKind"] == d0.device_kind
            assert s["devices"] == [str(d) for d in jax.devices()]
        ec.mark_ready()
        s = ec.stats()
        assert s["ready"] is True and "unreadyReason" not in s
        # Both flavors expose a registry the handler can scrape.
        for cell, kind in ((ec, "embedding"), (dc, "decoder")):
            fams = _parse_expo(render(cell.registry))
            assert "kukeon_cell_ready" in fams
            info = fams["kukeon_cell_info"]["samples"]
            assert any(lab.get("kind") == kind for _n, lab, _v in info)
        assert "kukeon_embed_sequences_total" in _parse_expo(
            render(ec.registry))
    finally:
        dc.engine.stop()
