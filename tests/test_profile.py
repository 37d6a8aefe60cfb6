"""Program timers and the flight recorder: per-program dispatch / token /
settled-seconds counters, the engine-step flight recorder (ring bounds,
concurrent ingest/readers, GET /v1/timeline), federation staleness, and
the `kuke timeline` renderer.

The acceptance spine: a flooded tiny engine counts its program work under
the names the benchmark reads (kukeon_program_dispatch_total,
kukeon_program_tokens_total) and states no utilization, and /v1/timeline
steps cross-link to trace ids the tracer resolves. The whole file must
stay green under KUKEON_SANITIZE=1 (check.yml runs it in both slices).
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.obs import (
    FlightRecorder,
    Registry,
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


@pytest.fixture(scope="module")
def flooded_engine():
    """One tiny engine after precompile + a request flood (measured busy
    time)."""
    eng = _tiny_engine()
    eng.precompile((8,))
    eng.warmup(8)
    reqs = [eng.submit(PROMPT, SamplingParams(max_new_tokens=12))
            for _ in range(2)]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    eng.timers.settle()
    return eng


def test_engine_flood_counts_program_work(flooded_engine):
    """After precompile + a flood the dispatch/tokens counters line up
    with the work."""
    eng = flooded_engine
    snap = eng.timers.snapshot()
    for program in ("prefill", "decode_chunk"):
        assert snap[program]["dispatches"] >= 1
        assert snap[program]["settled"] >= 1
        assert snap[program]["busy_s"] > 0.0
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


def test_scrape_counts_every_wrapped_program_and_states_no_utilization(
        flooded_engine):
    """The names benchmark/ reads off /metrics: every program the engine
    wrapped with the timer seam that dispatched has a
    kukeon_program_dispatch_total sample, every one that processed tokens
    a kukeon_program_tokens_total sample, and no utilization or static-cost
    family is on the scrape (a host-settled clock states none)."""
    eng = flooded_engine
    fams = _parse_expo(render(eng.registry))

    def by_program(fam):
        return {l["program"]: float(v) for _n, l, v in fams[fam]["samples"]}

    dispatched = by_program("kukeon_program_dispatch_total")
    tokens = by_program("kukeon_program_tokens_total")
    wrapped = set(eng.timers._timers)
    assert {"prefill", "insert", "decode_chunk"} <= wrapped
    assert set(dispatched) <= wrapped and set(tokens) <= wrapped
    snap = eng.timers.snapshot()
    for program in ("prefill", "insert", "decode_chunk"):
        assert dispatched[program] == snap[program]["dispatches"] >= 1
    for program in ("prefill", "decode_chunk"):
        assert tokens[program] == snap[program]["tokens"] >= 1
    for gone in ("kukeon_program_mfu", "kukeon_program_membw_util",
                 "kukeon_program_flops", "kukeon_program_hbm_bytes"):
        assert gone not in fams
    assert set(snap["decode_chunk"]) == {"dispatches", "settled", "busy_s",
                                         "tokens"}


# --- the live cell: /v1/timeline and what POST /v1/profile refuses -----------


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


def test_profile_route_refuses_fields_it_does_not_know(real_cell, tmp_path,
                                                       monkeypatch):
    """POST /v1/profile with a field the route does not know ({"layers":
    true} asked for a per-layer profile once) answers 400 with a message
    and starts NO capture; a plain body still starts one."""
    cell, port = real_cell
    # The module's cell was built before the per-test spool isolation:
    # give it a spool no other worker's captures (or pruning) can touch.
    monkeypatch.setattr(cell.profiler, "base_dir", str(tmp_path / "spool"))

    def names():
        status, raw = _get(port, "/v1/profile")
        assert status == 200
        return {c["name"] for c in json.loads(raw)["captures"]}

    assert names() == set()
    status, raw = _post(port, "/v1/profile", {"layers": True,
                                              "prefillLen": 8})
    assert status == 400
    err = json.loads(raw)["error"]
    assert "layers" in err and "durationMs" in err
    assert cell.profiler._active is None
    assert names() == set()
    status, raw = _post(port, "/v1/profile", {"durationMs": 50})
    assert status == 200 and json.loads(raw)["started"]
    started = json.loads(raw)["capture"]["name"]
    deadline = time.monotonic() + 30
    while cell.profiler._active is not None:
        assert time.monotonic() < deadline, "capture never completed"
        time.sleep(0.05)
    assert names() == {started}


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


def test_cli_has_no_profile_verb_and_keeps_top_and_timeline(capsys):
    """`kuke profile ...` is refused by the parser with the usage text
    (its one sub-command rendered the per-layer profiles); `kuke top` and
    `kuke timeline` still parse."""
    from kukeon_tpu.runtime import cli

    parser = cli.build_parser()
    with pytest.raises(SystemExit) as exc:
        parser.parse_args(["profile", "layers"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert err.startswith("usage: kuke") and "invalid choice: 'profile'" in err
    assert "profile" not in cli.HANDLERS
    assert parser.parse_args(["top"]).cmd == "top"
    args = parser.parse_args(["timeline", "ns/cell", "-n", "7"])
    assert (args.cmd, args.cell, args.n) == ("timeline", "ns/cell", 7)
    assert cli.HANDLERS["timeline"] is cli.cmd_timeline
