"""Roofline flight recorder (ISSUE 19): per-program timers and the MFU
gauges they derive, the per-layer cost profiler and its FLOPs-sum
contract, the engine-step flight recorder (ring bounds, concurrent
ingest/readers, GET /v1/timeline), federation staleness, and the
`kuke timeline` / `kuke profile layers` renderers.

The acceptance spine: a flooded tiny engine counts its program work and
exposes kukeon_program_mfu <= 1.0 exactly where the device's published
peak is known, `bench.py
--profile-layers`'s per-component FLOPs sum matches the whole-model
reference within 5%, and /v1/timeline steps cross-link to trace ids the
tracer resolves. The whole file must stay green under KUKEON_SANITIZE=1
(check.yml runs it in both slices).
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import numpy as np
import pytest

from kukeon_tpu import faults
from kukeon_tpu.models import llama
from kukeon_tpu.obs import (
    FlightRecorder,
    Registry,
    profile_layers,
    render,
)
from kukeon_tpu.obs import federate as fed
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

from test_obs import _parse_expo

PROMPT = np.arange(1, 9, dtype=np.int32)


def _tiny_engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 2)
    return ServingEngine(cfg, params, mesh, max_seq_len=96,
                         decode_chunk=4, **kw)


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp.status, raw


def _post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp.status, raw


# --- the flight-recorder ring ------------------------------------------------


def test_flight_recorder_ring_bounds_and_drop_counter():
    """Memory contract: the ring never holds more than its capacity, the
    overwritten records are counted both on .dropped and the
    kukeon_timeline_dropped_total counter, and snapshot(n) is the newest
    n oldest-first."""
    reg = Registry()
    rec = FlightRecorder(capacity=8, registry=reg)
    for i in range(20):
        rec.record({"tokens": i})
    assert len(rec) == 8
    assert rec.dropped == 12
    assert [s["seq"] for s in rec.snapshot()] == list(range(12, 20))
    assert [s["tokens"] for s in rec.snapshot(3)] == [17, 18, 19]
    assert rec.snapshot(0) == []
    # Every record got stamped with a wall-clock second.
    assert all(s["t"] > 0 for s in rec.snapshot())

    fams = _parse_expo(render(reg))
    assert fams["kukeon_timeline_dropped_total"]["type"] == "counter"
    [(_n, _l, dropped)] = fams["kukeon_timeline_dropped_total"]["samples"]
    assert float(dropped) == 12.0
    [(_n, _l, depth)] = fams["kukeon_timeline_depth"]["samples"]
    assert float(depth) == 8.0


def test_flight_recorder_concurrent_flood():
    """Satellite: ingest hammers from several threads while readers flood
    snapshot() and the registry scrape — no torn reads, ring stays
    bounded, every drop accounted. Green under KUKEON_SANITIZE=1."""
    reg = Registry()
    rec = FlightRecorder(capacity=64, registry=reg)
    writers, per_writer = 4, 300
    stop = threading.Event()
    errors: list[BaseException] = []

    def hammer(base):
        try:
            for i in range(per_writer):
                rec.record({"tokens": base + i})
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                snap = rec.snapshot(16)
                seqs = [s["seq"] for s in snap]
                assert seqs == sorted(seqs)       # oldest-first, no tears
                assert len(snap) <= 64
                render(reg)                        # scrape-path collector
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i * per_writer,))
               for i in range(writers)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers + threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors[0]
    total = writers * per_writer
    assert len(rec) == 64
    assert rec.dropped == total - 64
    fams = _parse_expo(render(reg))
    [(_n, _l, dropped)] = fams["kukeon_timeline_dropped_total"]["samples"]
    assert float(dropped) == float(total - 64)


# --- per-program timers: the engine flood ------------------------------------


class _FakeDevice:
    """Stands in for jax.devices()[0] at scrape time: what obs/profile's
    peak table and obs/device's HBM collector read off a device."""

    id = 0

    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind

    def memory_stats(self):
        return None


@pytest.fixture(scope="module")
def flooded_engine():
    """One tiny engine after precompile (static costs) + a request flood
    (measured busy time) — shared by every peak-table case below."""
    eng = _tiny_engine()
    eng.precompile((8,))      # cost_analysis denominators land here
    eng.warmup(8)
    reqs = [eng.submit(PROMPT, SamplingParams(max_new_tokens=12))
            for _ in range(2)]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    eng.timers.settle()
    return eng


def test_engine_flood_counts_program_work(flooded_engine):
    """After precompile + a flood the dispatch/tokens/cost counters line up
    with the work, whatever the device's peak is."""
    eng = flooded_engine
    snap = eng.timers.snapshot()
    for program in ("prefill", "decode_chunk"):
        assert snap[program]["dispatches"] >= 1
        assert snap[program]["settled"] >= 1
        assert snap[program]["busy_s"] > 0.0
        assert snap[program]["flops"] > 0.0          # CPU reports costs
    # Decode counted batch*k token work; prefill counted the prompt rows.
    assert snap["decode_chunk"]["tokens"] >= 2 * 12
    assert snap["prefill"]["tokens"] >= 2 * len(PROMPT)
    fams = _parse_expo(render(eng.registry))
    # Histogram of settled wall times exists per program.
    assert any(l.get("program") == "decode_chunk"
               for _n, l, _v in fams["kukeon_program_seconds"]["samples"])
    # The engine's flight recorder saw the same flood.
    assert len(eng.recorder) >= 1
    step = eng.recorder.snapshot(1)[0]
    for key in ("seq", "t", "wall_s", "occupancy", "slots", "tokens",
                "programs", "traces", "queue_depth"):
        assert key in step


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", "empty"),
    ("tpu", "TPU v5 lite", "gauges"),
    ("tpu", "TPU v9 imaginary", "absent-with-reason"),
])
def test_utilization_gauges_follow_the_peak_table(
        flooded_engine, monkeypatch, platform, kind, want):
    """MFU / bandwidth gauges exist only where the device's published peak
    is known: computed on a listed TPU kind, declared-but-empty on the
    CPU (like the HBM families), and ABSENT with the reason in the HELP
    text on a TPU that is not in the table — never from a made-up peak."""
    eng = flooded_engine
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    text = render(eng.registry)
    fams = _parse_expo(text)
    snap = eng.timers.snapshot()
    for fam in ("kukeon_program_mfu", "kukeon_program_membw_util"):
        assert fams[fam]["type"] == "gauge"            # always declared
        values = {l["program"]: float(v)
                  for _n, l, v in fams[fam]["samples"]}
        if want == "gauges":
            for program in ("prefill", "decode_chunk"):
                assert 0.0 < values[program] <= 1.0
            # (rounded to 6 digits: a tiny CPU run against a v5e peak is 0.0)
            assert 0.0 <= snap["decode_chunk"]["mfu"] <= 1.0
        else:
            assert values == {}
            assert snap["decode_chunk"]["mfu"] is None
            assert snap["decode_chunk"]["membw_util"] is None
        help_line = next(ln for ln in text.splitlines()
                         if ln.startswith(f"# HELP {fam} "))
        assert ("ABSENT" in help_line) == (want == "absent-with-reason")
        if want == "absent-with-reason":
            assert "TPU v9 imaginary" in help_line


def test_peak_table_is_keyed_by_the_runtime_device_kind(monkeypatch):
    """`TPU v5 lite` — what the installed runtime calls a v5e — resolves to
    the published 197 TFLOP/s bf16 / 819 GB/s; a substring such as "v5e"
    is not a key."""
    from kukeon_tpu.obs import device_peaks
    from kukeon_tpu.obs.profile import PEAKS_BY_DEVICE_KIND

    assert PEAKS_BY_DEVICE_KIND["TPU v5 lite"] == (197e12, 819e9)
    assert "v5e" not in PEAKS_BY_DEVICE_KIND
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
    assert device_peaks() == ((197e12, 819e9), "")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v5e")])
    peaks, why = device_peaks()
    assert peaks is None and "TPU v5e" in why
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("cpu", "cpu")])
    assert device_peaks() == (None, "")


# --- the per-layer cost profiler ---------------------------------------------


def test_profile_layers_flops_sum_matches_whole_model():
    """Acceptance: per-component prefill FLOPs sum to the whole-model
    reference within 5% (the scan-free lowering makes this structural,
    not lucky), with one entry per component."""
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    prof = profile_layers(params, cfg, prefill_len=16, decode_batch=2,
                          measure=False)
    assert prof["schema"] == "kukeon-layer-profile/v1"
    assert prof["errors"] == 0
    names = [c["name"] for c in prof["components"]]
    assert names == ["embed"] + [f"layer{i}" for i in
                                 range(cfg.num_layers)] + ["head"]
    assert prof["model_flops"] > 0
    total = sum(c["prefill"]["flops"] for c in prof["components"])
    assert abs(total - prof["model_flops"]) / prof["model_flops"] < 0.05
    # Both shapes costed for every component.
    for c in prof["components"]:
        for shape in ("prefill", "decode"):
            assert c[shape]["flops"] > 0
            assert c[shape]["bytes"] > 0


def test_profile_layers_measures_wall_time():
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    prof = profile_layers(params, cfg, prefill_len=8, decode_batch=1,
                          measure=True, reps=1)
    assert prof["errors"] == 0
    assert all(c["prefill"]["wall_s"] >= 0 for c in prof["components"])


def test_profile_layers_armed_fault_degrades_cleanly():
    """Satellite: the profile.layers fault point. Armed at probability 1
    every component records an error entry instead of raising — a
    partial/empty profile, never a dead caller."""
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    os.environ[faults.ENV] = "profile.layers:1"
    prof = profile_layers(params, cfg, prefill_len=8, decode_batch=1,
                          measure=False)
    # embed + layers + head each failed; the whole-model reference does
    # not pass through the fault point, so it may still cost out.
    assert prof["errors"] >= cfg.num_layers + 2
    failed = [c for c in prof["components"] if c.get("error")]
    assert len(failed) >= cfg.num_layers + 2
    assert all("FaultInjected" in c["error"] for c in failed)


# --- the live cell: /v1/timeline and POST /v1/profile {"layers": true} -------


@pytest.fixture(scope="module")
def real_cell():
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    cell = ServingCell("tiny", num_slots=2, max_seq_len=96, checkpoint=None,
                       dtype=None, max_pending=8)
    cell.warmup(prompt_len=16)
    cell.engine.start()
    cell.mark_ready()
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield cell, server.server_address[1]
    server.shutdown()
    server.server_close()
    cell.engine.stop()


def test_timeline_endpoint_cross_links_to_traces(real_cell):
    """Acceptance: GET /v1/timeline reconstructs the engine's recent
    steps, and the trace ids seated in those steps resolve through the
    same tracer `kuke trace` reads."""
    cell, port = real_cell
    status, raw = _post(port, "/v1/generate",
                        {"promptTokens": [1, 2, 3, 4], "maxNewTokens": 4})
    assert status == 200 and json.loads(raw)["numTokens"] == 4

    # The engine thread records the step before the terminal token by a
    # hair's width — poll briefly for a step that carries a trace id.
    deadline = time.monotonic() + 5.0
    tids: set[str] = set()
    while not tids and time.monotonic() < deadline:
        status, raw = _get(port, "/v1/timeline?n=50")
        assert status == 200
        body = json.loads(raw)
        tids = {t for s in body["steps"] for t in (s.get("traces") or ())}
        if not tids:
            time.sleep(0.01)
    assert body["capacity"] == cell.engine.recorder.capacity
    assert body["steps"], "flight recorder saw no steps"
    for step in body["steps"]:
        assert step["slots"] == 2
        assert step["wall_s"] >= 0
        assert isinstance(step["programs"], dict)
    assert tids, "no step carried a seated trace id"
    # The span lands in the tracer ring when the engine thread finishes
    # it — a hair after the terminal token is emitted. Poll briefly.
    while (not any(cell.engine.tracer.for_trace(t) for t in tids)
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert any(cell.engine.tracer.for_trace(t) for t in tids)

    status, _raw = _get(port, "/v1/timeline?n=bogus")
    assert status == 400


def test_cell_layer_profile_over_http_persists(real_cell, monkeypatch,
                                               tmp_path):
    """POST /v1/profile {"layers": true} profiles the live model and
    persists next to the serving tune; `kuke profile layers` renders the
    stored profile without touching jax."""
    from kukeon_tpu.runtime.cli import render_layer_profile
    from kukeon_tpu.serving import tuning

    cell, port = real_cell
    store = tmp_path / "layer_profile.json"
    monkeypatch.setenv("KUKEON_LAYER_PROFILE_PATH", str(store))
    status, raw = _post(port, "/v1/profile",
                        {"layers": True, "prefillLen": 8, "decodeBatch": 2})
    assert status == 200
    prof = json.loads(raw)
    assert prof["errors"] == 0
    assert prof["path"] == str(store)
    assert "|" in prof["key"]

    stored = tuning.load_layer_profiles()
    assert prof["key"] in stored
    assert stored[prof["key"]]["profiled_at"]
    out = render_layer_profile(prof["key"], stored[prof["key"]])
    assert "COMPONENT" in out and "layer0" in out and "prefill" in out


def test_cell_layer_profile_fault_recorded_not_fatal(real_cell):
    """Satellite, the other fault branch: an armed profile.layers fault
    during an HTTP-triggered profile comes back RECORDED in the body
    (200, errors counted, nothing persisted) and the cell keeps
    serving."""
    cell, port = real_cell
    os.environ[faults.ENV] = "profile.layers:1"
    try:
        status, raw = _post(port, "/v1/profile", {"layers": True,
                                                  "prefillLen": 8,
                                                  "decodeBatch": 1})
    finally:
        os.environ.pop(faults.ENV, None)
        faults.reset()
    assert status == 200
    prof = json.loads(raw)
    assert prof["errors"] > 0
    assert "path" not in prof                    # partial -> not persisted
    status, raw = _post(port, "/v1/generate",
                        {"promptTokens": [1, 2, 3], "maxNewTokens": 2})
    assert status == 200 and json.loads(raw)["numTokens"] == 2


# --- federation: fetch_timelines + scrape staleness --------------------------


def test_fetch_timelines_unions_sorts_and_tags():
    """The daemon-side union: steps from every reachable cell come back
    tagged with the cell key and sorted by wall-clock stamp; dead cells
    contribute nothing (and never raise)."""
    from kukeon_tpu.runtime.daemon import fetch_timelines

    steps = [{"seq": 1, "t": 20.0}, {"seq": 0, "t": 10.0}]

    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            assert self.path == "/v1/timeline?n=5"
            body = json.dumps({"steps": steps}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        got = fetch_timelines([("ns/c0", url, {}),
                               ("ns/dead", "http://127.0.0.1:9", {})],
                              n=5, timeout_s=5.0)
    finally:
        srv.shutdown()
        srv.server_close()
    assert [s["seq"] for s in got] == [0, 1]          # re-sorted by t
    assert all(s["cell"] == "ns/c0" for s in got)


def test_telemetry_scrape_ages_track_last_good_and_departures():
    """Satellite: kukeon_cell_scrape_age_seconds bookkeeping. A failing
    cell's age grows from its last GOOD scrape; a departed cell's age is
    forgotten with the cell; a cell never seen good contributes no
    sample."""
    from kukeon_tpu.runtime.daemon import FleetTelemetry

    now = [100.0]
    telem = FleetTelemetry(None, registry=Registry(),
                           clock=lambda: now[0], rules=[])
    ages = telem.note_scrapes([{"cell": "a", "ok": True},
                               {"cell": "b", "ok": False}], at=100.0)
    assert ages == {"a": 0.0}                         # b never seen good
    ages = telem.note_scrapes([{"cell": "a", "ok": False},
                               {"cell": "b", "ok": True}], at=107.0)
    assert ages == {"a": 7.0, "b": 0.0}
    now[0] = 109.0
    assert telem.scrape_ages() == {"a": 9.0, "b": 2.0}
    # "a" left the fleet: its frozen age must not read "stale" forever.
    ages = telem.note_scrapes([{"cell": "b", "ok": True}], at=110.0)
    assert ages == {"b": 0.0}
    assert telem.scrape_ages(at=111.0) == {"b": 1.0}

    fam = fed.scrape_age_family(telem.scrape_ages(at=111.5))
    assert fam.name == "kukeon_cell_scrape_age_seconds"
    assert fam.samples == [("kukeon_cell_scrape_age_seconds",
                            {"cell": "b"}, "1.500")]


def test_scrape_age_family_sorts_and_clamps():
    fam = fed.scrape_age_family({"z": 2.0, "a": -0.5})
    assert [(s[1]["cell"], s[2]) for s in fam.samples] == [
        ("a", "0.000"), ("z", "2.000")]


# --- renderers ---------------------------------------------------------------


def test_render_timeline_table():
    from kukeon_tpu.runtime.cli import render_timeline

    steps = [
        {"t": 1000.25, "seq": 4, "wall_s": 0.012, "occupancy": 2,
         "slots": 4, "chunk_k": 8, "tokens": 16, "fetches": 1,
         "uploads": 0, "preemptions": 0, "queue_depth": 3,
         "programs": {"decode_chunk": 0.0101}, "traces": ["abc123"],
         "cell": "ns/c0"},
        {"t": 1000.0, "seq": 3, "wall_s": 0.5, "occupancy": 1, "slots": 4,
         "tokens": 1},
    ]
    out = render_timeline(steps)
    lines = out.splitlines()
    assert "SEQ" in lines[0] and "TOKENS" in lines[0]
    # Sorted by wall-clock stamp: seq 3 first despite list order.
    assert lines[1].split()[1] == "3"
    assert "+0.000s" in lines[1] and "+0.250s" in lines[2]
    assert "2/4" in lines[2]
    assert "decode_chunk 10.1ms" in lines[2]
    assert "traces=abc123" in lines[2] and "[ns/c0]" in lines[2]
    assert "no recorded engine steps" in render_timeline([])


def test_render_layer_profile_marks_failed_components():
    from kukeon_tpu.runtime.cli import render_layer_profile

    prof = {"schema": "kukeon-layer-profile/v1", "num_layers": 2,
            "prefill_len": 16, "decode_batch": 2, "model_flops": 1.2e7,
            "model_bytes": 3.4e6, "errors": 1,
            "components": [
                {"name": "embed",
                 "prefill": {"flops": 2144.0, "bytes": 268.0,
                             "wall_s": 0.001},
                 "decode": {"flops": 268.0, "bytes": 34.0}},
                {"name": "layer0", "error": "FaultInjected: boom"},
            ]}
    out = render_layer_profile("tiny|cpu|1", prof)
    assert "tiny|cpu|1" in out
    assert "1 component(s) failed to profile" in out
    assert "(FaultInjected: boom)" in out
    assert "1.00ms" in out                         # measured wall column
    assert "model_flops=12.0M" in out


def test_render_top_dims_stale_rows(monkeypatch):
    """Satellite: a row whose last good scrape is older than 2 scrape
    intervals renders ANSI-dim; fresh rows render normally."""
    from kukeon_tpu.runtime.cli import render_top

    monkeypatch.delenv("KUKEON_SCRAPE_INTERVAL_S", raising=False)
    row = {"cell": "ns/fresh", "model": "tiny", "ready": True, "ok": True,
           "qps": 1.0, "queueDepth": 0, "restarts": 0}
    stale = dict(row, cell="ns/stale", scrapeAgeS=21.0)   # > 2 * 10s
    out = render_top([row, stale])
    fresh_line = next(ln for ln in out.splitlines() if "ns/fresh" in ln)
    stale_line = next(ln for ln in out.splitlines() if "ns/stale" in ln)
    assert not fresh_line.startswith("\x1b[2m")
    assert stale_line.startswith("\x1b[2m") and stale_line.endswith("\x1b[0m")
    # Tighter interval drags the threshold down with it.
    monkeypatch.setenv("KUKEON_SCRAPE_INTERVAL_S", "2")
    out = render_top([dict(row, scrapeAgeS=5.0)])
    assert out.splitlines()[-1].startswith("\x1b[2m")


# --- bench artifact v8 -------------------------------------------------------


def test_bench_compare_upgrades_v7_and_diffs_mfu(tmp_path):
    """v7 artifacts upgrade in place (program_costs/mfu default None —
    reported as n/a, never a regression) and an MFU drop past the
    threshold flags with higher-is-better polarity."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_compare_v8", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)

    old = tmp_path / "BENCH_r1.json"
    old.write_text(json.dumps({"schema": "kukeon-bench/v7",
                               "tok_per_s": 100.0}))
    art = bc.read_artifact(str(old))
    assert art["schema"] == "kukeon-bench/v8"
    assert art["program_costs"] is None and art["mfu"] is None

    new = dict(art, schema="kukeon-bench/v8", mfu=0.5,
               program_costs={"decode_chunk": {"mfu": 0.5}})
    prev = dict(art, mfu=0.9)
    rows, regressed = bc.compare(prev, new, threshold_pct=10.0)
    mfu_row = next(r for r in rows if r[0] == "MFU")
    assert mfu_row[4] == "REGRESSION" and regressed
    # Missing on one side: informational, never a regression.
    rows, regressed = bc.compare(art, new, threshold_pct=10.0)
    assert next(r for r in rows if r[0] == "MFU")[4] == "n/a"
    assert not regressed
