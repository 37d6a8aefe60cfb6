"""End-to-end distributed tracing (ISSUE 9): W3C-style context
propagation gateway -> replica -> engine, federated trace reconstruction,
tail sampling, histogram exemplars, cold-start boot spans, and the
`kuke trace` timeline renderer.

The acceptance spine lives in
test_retry_on_second_replica_yields_one_trace: a request issued through
the gateway that is retried onto a second replica yields ONE trace whose
union (gateway proxy span + both replica attempts + engine phase spans)
reconstructs across components, with the engine phases partitioning the
request's wall time.
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
    Registry,
    Tracer,
    expo,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    render,
)
from kukeon_tpu.obs import federate as fed
from kukeon_tpu.obs import trace as obs_trace
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

PROMPT = np.arange(1, 9, dtype=np.int32)


def _tiny_engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 1)
    return ServingEngine(cfg, params, mesh, max_seq_len=96,
                         decode_chunk=4, **kw)


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp.status, raw


def _post(port, path, body, headers=None, timeout=60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, body=json.dumps(body),
                 headers={"Content-Type": "application/json",
                          **(headers or {})})
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp.status, raw


# --- context plumbing --------------------------------------------------------


def test_traceparent_roundtrip_and_rejects_garbage():
    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    ctx = parse_traceparent(format_traceparent(tid, sid))
    assert ctx is not None
    assert ctx.trace_id == tid and ctx.span_id == sid
    for bad in (None, "", "junk", "00-short-deadbeef00000000-01",
                "00-" + "g" * 32 + "-" + "0" * 16 + "-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero ids
                format_traceparent(tid, sid) + "-extra"):
        assert parse_traceparent(bad) is None, bad


def test_span_joins_context_and_mints_when_absent():
    t = Tracer()
    ctx = obs_trace.TraceContext(trace_id=new_trace_id(),
                                 span_id=new_span_id())
    child = t.begin(1, 4, trace_ctx=ctx)
    assert child.trace_id == ctx.trace_id
    assert child.parent_span_id == ctx.span_id
    assert child.span_id != ctx.span_id
    root = t.begin(2, 4)
    assert len(root.trace_id) == 32 and root.parent_span_id is None
    d = t.finish(child, "ok").to_dict()
    assert d["traceId"] == ctx.trace_id
    assert d["parentSpanId"] == ctx.span_id
    assert d["spanId"] == child.span_id


# --- tail sampling -----------------------------------------------------------


def _span_with_e2e(t: Tracer, rid: int, e2e_s: float, **kw):
    """A span whose e2e is pinned by back-dating its root event."""
    return t.begin(rid, 4, start_mono=time.monotonic() - e2e_s, **kw)


def test_tail_sampler_flood_keeps_what_matters():
    """Acceptance: under a flood with keep-probability 0 the sampler
    provably retains 100% of error/preempted/retried traces and the slow
    tail while dropping every boring fast-path one."""
    # Boring spans pin a ~40ms e2e by back-dating the root event: the few
    # microseconds between begin and finish ride on top, so the pinned
    # value sits mid-bucket ((32ms, 64ms]) with ~24ms of scheduler-jitter
    # headroom — a loaded CI box can't accidentally promote one into a
    # higher bucket and trip the keep-the-slow-tail rule.
    t = Tracer(capacity=2048, keep_probability=0.0)
    boring = [t.finish(_span_with_e2e(t, i, 0.04), "ok")
              for i in range(300)]
    errors = [t.finish(_span_with_e2e(t, 1000 + i, 0.04), "error")
              for i in range(40)]
    timeouts = [t.finish(_span_with_e2e(t, 2000 + i, 0.04), "timeout")
                for i in range(40)]
    preempted = []
    for i in range(40):
        s = _span_with_e2e(t, 3000 + i, 0.04)
        s.event("preempted")
        preempted.append(t.finish(s, "ok"))
    retried = []
    for i in range(40):
        s = _span_with_e2e(t, 4000 + i, 0.04)
        s.attrs["retries"] = 1
        retried.append(t.finish(s, "ok"))
    # One genuinely slow ok span: kept by the p95+ rule alone.
    slow = t.finish(_span_with_e2e(t, 9999, 10.0), "ok")

    kept_ids = {d["spanId"] for d in t.recent(4096)}
    for group in (errors, timeouts, preempted, retried):
        assert all(s.span_id in kept_ids for s in group)   # 100% retention
    assert slow.span_id in kept_ids
    assert not any(s.span_id in kept_ids for s in boring)
    assert t.sample_stats["dropped"] == len(boring)
    assert t.sample_stats["kept"] == 161


def test_tail_sampler_default_keeps_everything():
    t = Tracer(capacity=64)   # KUKEON_TRACE_SAMPLE unset -> keep 1.0
    for i in range(10):
        t.finish(_span_with_e2e(t, i, 0.0006), "ok")
    assert len(t) == 10 and t.sample_stats["dropped"] == 0


def test_tail_sampler_verdict_is_deterministic_per_trace():
    """The probabilistic decision hashes the trace id, so every component
    of one trace (gateway + N engines) reaches the same verdict."""
    t1 = Tracer(keep_probability=0.5)
    t2 = Tracer(keep_probability=0.5)
    for i in range(64):
        tid = new_trace_id()
        ctx = obs_trace.TraceContext(trace_id=tid, span_id=new_span_id())
        t1.finish(t1.begin(i, 1, trace_ctx=ctx), "ok")
        t2.finish(t2.begin(i, 1, trace_ctx=ctx), "ok")
        in1 = bool(t1.for_trace(tid))
        in2 = bool(t2.for_trace(tid))
        assert in1 == in2


# --- engine integration ------------------------------------------------------


def test_engine_span_joins_propagated_context_and_attaches_exemplars():
    eng = _tiny_engine()
    ctx = obs_trace.TraceContext(trace_id=new_trace_id(),
                                 span_id=new_span_id())
    req = eng.submit(PROMPT, SamplingParams(max_new_tokens=4),
                     trace_ctx=ctx)
    while not req.done.is_set():
        eng.step()
    spans = eng.tracer.for_trace(ctx.trace_id)
    assert len(spans) == 1
    span = spans[0]
    assert span["parentSpanId"] == ctx.span_id
    assert span["outcome"] == "ok" and span["tokens"] == 4
    # Phase durations partition the request's wall time.
    assert abs(sum(span["phasesS"].values()) - span["e2eS"]) < 1e-3
    # TTFT and e2e histograms carry the trace id as a bucket exemplar.
    for metric in ("kukeon_engine_ttft_seconds", "kukeon_engine_e2e_seconds"):
        ex = eng.registry.get(metric).exemplars()
        assert ctx.trace_id in {tid for _v, tid in ex.values()}, metric
    # The exemplar rides the exposition as a parseable comment line and
    # the tail-sampler verdict family is rendered.
    fams = fed.parse(render(eng.registry))
    assert any(tid == ctx.trace_id for _n, _l, tid, _v
               in fams["kukeon_engine_ttft_seconds"].exemplars)
    kept = {lab["decision"]: float(v) for _n, lab, v in
            fams["kukeon_trace_tail_sampled_total"].samples}
    assert kept["kept"] >= 1


def test_engine_shed_span_joins_the_callers_trace():
    """A 429'd hop is part of the SAME trace: a gateway retry that sheds
    on replica A and succeeds on replica B leaves a shed span on A with
    the shared trace id."""
    eng = _tiny_engine(max_pending=1)
    ctx = obs_trace.TraceContext(trace_id=new_trace_id(),
                                 span_id=new_span_id())
    held = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    from kukeon_tpu.serving import RejectedError

    with pytest.raises(RejectedError):
        eng.submit(PROMPT, SamplingParams(max_new_tokens=2), trace_ctx=ctx)
    spans = eng.tracer.for_trace(ctx.trace_id)
    assert [s["outcome"] for s in spans] == ["shed"]
    assert spans[0]["parentSpanId"] == ctx.span_id
    held.cancel()
    while not held.done.is_set():
        eng.step()


def test_preempt_resume_keeps_one_continuous_span(monkeypatch):
    """Paged-KV preemption continuity: the victim's span survives the
    preempt+resume cycle as ONE span (same trace id), its events record
    the preemption and the re-prefill, and the tail sampler keeps it even
    at keep-probability 0."""
    monkeypatch.setenv(obs_trace.TRACE_SAMPLE_ENV, "0")
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=3, max_seq_len=128,
                        decode_chunk=4, kv_page_tokens=16, kv_pool_pages=8,
                        prefix_cache_size=0)
    assert eng.tracer.keep_probability == 0.0
    sp = SamplingParams(max_new_tokens=40, temperature=0.8)
    reqs = [eng.submit(np.arange(1, 40, dtype=np.int32), sp)
            for _ in range(3)]
    n = 0
    while not all(r.done.is_set() for r in reqs) and n < 800:
        eng.step()
        n += 1
    assert all(r.done.is_set() and r.error is None for r in reqs)
    victims = [r for r in reqs if r.preemptions > 0]
    assert victims
    for r in victims:
        spans = eng.tracer.for_trace(r.trace.trace_id)
        assert len(spans) == 1                   # one continuous span
        events = [e["event"] for e in spans[0]["events"]]
        assert "preempted" in events
        # Resume re-prefills: a second prefill_dispatched after preempted.
        assert events.index("preempted") < len(events) - 1
        assert events.count("prefill_dispatched") >= 2
        assert spans[0]["outcome"] == "ok"


# --- gateway propagation -----------------------------------------------------


class _Replica:
    """Minimal serving-cell stand-in: records every traceparent header it
    receives; scripted to shed 429 or stream exact bytes."""

    def __init__(self, shed_429: bool = False,
                 stream_script: bytes | None = None):
        self.shed_429 = shed_429
        self.stream_script = stream_script
        self.traceparents: list[str | None] = []
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *a):
                pass

            def _json(self, code, obj, headers=None):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/v1/stats":
                    self._json(200, {"model": "tiny", "ready": True,
                                     "draining": False, "queueDepth": 0})
                else:
                    self._json(200, {"status": "ok"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                outer.traceparents.append(self.headers.get("traceparent"))
                if outer.shed_429:
                    self._json(429, {"error": "queue full"},
                               {"Retry-After": "1"})
                    return
                if req.get("stream") and outer.stream_script is not None:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.end_headers()
                    self.wfile.write(outer.stream_script)
                    self.wfile.flush()
                    return
                self._json(200, {"tokens": [1, 2], "text": "xx",
                                 "numTokens": 2, "seconds": 0.0})

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def kill(self):
        self.server.shutdown()
        self.server.server_close()


def _gateway(urls):
    from kukeon_tpu.gateway.cell import GatewayCell, make_gateway_handler

    # spill_capacity=0: these tests pin the shed SPAN story; an all-shed
    # request must terminate immediately instead of parking in the
    # spillover queue (whose spans are covered in tests/test_scaler.py).
    gw = GatewayCell("tiny", urls, poll_interval_s=0.05,
                     request_timeout_s=30.0, spill_capacity=0)
    gw.start()
    srv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    gw.router.poll_once()
    return gw, srv, srv.server_address[1]


def test_gateway_mints_context_and_propagates_downstream():
    rep = _Replica()
    gw, srv, port = _gateway([rep.url])
    try:
        status, _ = _post(port, "/v1/generate",
                          {"promptTokens": [1, 2], "maxNewTokens": 2})
        assert status == 200
        assert len(rep.traceparents) == 1
        ctx = parse_traceparent(rep.traceparents[0])
        assert ctx is not None                       # minted at the gateway
        spans = gw.tracer.for_trace(ctx.trace_id)
        assert len(spans) == 1
        span = spans[0]
        assert span["component"] == "gateway"
        assert span["spanId"] == ctx.span_id         # engine hangs under it
        assert span["outcome"] == "ok"
        assert span["attrs"]["replica"] == "r0"
        events = [e["event"] for e in span["events"]]
        assert "proxy_attempt" in events
    finally:
        srv.shutdown()
        srv.server_close()
        gw.stop()
        rep.kill()


def test_gateway_joins_client_supplied_traceparent():
    rep = _Replica()
    gw, srv, port = _gateway([rep.url])
    client_tid, client_sid = new_trace_id(), new_span_id()
    try:
        status, _ = _post(
            port, "/v1/generate",
            {"promptTokens": [1, 2], "maxNewTokens": 2},
            headers={"traceparent":
                     format_traceparent(client_tid, client_sid)})
        assert status == 200
        spans = gw.tracer.for_trace(client_tid)
        assert len(spans) == 1
        assert spans[0]["parentSpanId"] == client_sid
        # Downstream got the GATEWAY's span as parent, same trace id.
        ctx = parse_traceparent(rep.traceparents[0])
        assert ctx.trace_id == client_tid
        assert ctx.span_id == spans[0]["spanId"]
    finally:
        srv.shutdown()
        srv.server_close()
        gw.stop()
        rep.kill()


def test_stream_passthrough_stays_byte_exact_with_trace_context():
    """Context travels in headers, never the body: the ndjson relay is
    byte-for-byte identical while the trace context still reaches the
    replica and the gateway span records the streamed outcome."""
    script = (b'{"token": 1, "text": "\xc3\xa9"}\n'
              b'{"error": "mid-stream"}\n'
              b'{"done": true, "numTokens": 1}\n')
    rep = _Replica(stream_script=script)
    gw, srv, port = _gateway([rep.url])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/generate",
                     body=json.dumps({"promptTokens": [1], "stream": True}),
                     headers={"Content-Type": "application/json",
                              "traceparent": format_traceparent(
                                  new_trace_id(), new_span_id())})
        resp = conn.getresponse()
        raw = resp.read()
        conn.close()
        assert resp.status == 200
        assert raw == script                         # byte-exact
        assert parse_traceparent(rep.traceparents[0]) is not None
        span = gw.tracer.recent(1)[0]
        assert span["outcome"] == "ok" and span["attrs"].get("stream")
    finally:
        srv.shutdown()
        srv.server_close()
        gw.stop()
        rep.kill()


def test_gateway_trace_endpoint_serves_proxy_spans():
    rep = _Replica(shed_429=True)
    gw, srv, port = _gateway([rep.url])
    try:
        status, _ = _post(port, "/v1/generate",
                          {"promptTokens": [1], "maxNewTokens": 1})
        assert status == 429                         # all replicas shed
        status, raw = _get(port, "/v1/trace?n=5")
        assert status == 200
        spans = json.loads(raw)["spans"]
        assert spans and spans[0]["outcome"] == "shed"
        events = [e["event"] for e in spans[0]["events"]]
        assert "proxy_retry" in events and "proxy_shed" in events
        # trace_id / request_id filters answer too.
        tid = spans[0]["traceId"]
        status, raw = _get(port, f"/v1/trace?trace_id={tid}")
        assert json.loads(raw)["spans"][0]["traceId"] == tid
        status, raw = _get(port, "/v1/trace?request_id=abc")
        assert status == 400
    finally:
        srv.shutdown()
        srv.server_close()
        gw.stop()
        rep.kill()


# --- the acceptance spine: retry onto a second replica = ONE trace -----------


@pytest.fixture(scope="module")
def real_cell():
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    cell = ServingCell("tiny", num_slots=2, max_seq_len=96, checkpoint=None,
                       dtype=None, max_pending=8)
    # Warmup before the engine thread starts (step() is single-driver);
    # also stamps the compile/warmup boot marks finish_boot() exports.
    cell.warmup(prompt_len=16)
    cell.engine.start()
    cell.mark_ready()
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield cell, server.server_address[1]
    server.shutdown()
    server.server_close()
    cell.engine.stop()


def test_retry_on_second_replica_yields_one_trace(real_cell):
    """A request retried onto a second replica yields ONE trace: the
    gateway proxy span records both replica attempts and the retry hop,
    the winning replica's engine span joins as a child, the federated
    union reconstructs the whole timeline, and the engine phases
    partition the request's wall time."""
    from kukeon_tpu.runtime import daemon as d
    from kukeon_tpu.runtime.cli import render_trace

    cell, cell_port = real_cell
    shedding = _Replica(shed_429=True)               # becomes r0 (tie-break)
    gw, srv, port = _gateway([shedding.url, f"http://127.0.0.1:{cell_port}"])
    try:
        status, raw = _post(port, "/v1/generate",
                            {"promptTokens": [1, 2, 3], "maxNewTokens": 3})
        assert status == 200 and json.loads(raw)["numTokens"] == 3

        # The gateway span: two attempts, one retry hop, outcome ok on r1.
        gspan = next(s for s in gw.tracer.recent(10)
                     if s["outcome"] == "ok")
        tid = gspan["traceId"]
        attempts = [e["attrs"]["replica"] for e in gspan["events"]
                    if e["event"] == "proxy_attempt"]
        assert attempts == ["r0", "r1"]
        retries = [e for e in gspan["events"] if e["event"] == "proxy_retry"]
        assert len(retries) == 1
        assert retries[0]["attrs"]["reason"] == "status_429"
        assert gspan["attrs"]["retries"] == 1

        # Both hops carried the SAME trace id downstream.
        assert [parse_traceparent(h).trace_id
                for h in shedding.traceparents] == [tid]

        # The winning replica's engine span is a child of the gateway span.
        # (The HTTP response can race the engine thread's span finish by a
        # few microseconds — the terminal token is emitted before the span
        # moves into the ring — so poll briefly.)
        deadline = time.monotonic() + 5.0
        espans = cell.engine.tracer.for_trace(tid)
        while not espans and time.monotonic() < deadline:
            time.sleep(0.01)
            espans = cell.engine.tracer.for_trace(tid)
        assert len(espans) == 1
        espan = espans[0]
        assert espan["parentSpanId"] == gspan["spanId"]
        assert espan["outcome"] == "ok" and espan["tokens"] == 3
        assert abs(sum(espan["phasesS"].values()) - espan["e2eS"]) < 1e-3

        # Federated reconstruction (the Traces RPC's machinery) unions the
        # gateway ring and the replica ring into one timeline.
        endpoints = [("default/default/default/llm",
                      f"http://127.0.0.1:{port}", {}),
                     ("default/default/default/llm/r1",
                      f"http://127.0.0.1:{cell_port}", {})]
        spans = d.fetch_traces(endpoints, trace_id=tid, timeout_s=10.0)
        assert {s["cell"] for s in spans} == {e[0] for e in endpoints}
        assert {s["component"] for s in spans} == {"gateway", "engine"}
        assert all(s["traceId"] == tid for s in spans)
        # Sorted by wall-clock start: the gateway span leads.
        assert spans[0]["component"] == "gateway"

        # The `kuke trace` renderer lays the whole thing out.
        out = render_trace(tid, spans)
        assert "gateway" in out and "engine" in out
        assert "attempts r0!status_429 -> r1" in out
        assert "default/default/default/llm/r1" in out
        assert "3 tokens" in out
    finally:
        srv.shutdown()
        srv.server_close()
        gw.stop()
        shedding.kill()


def test_fetch_traces_skips_dead_and_traceless_cells():
    """Federation degrades span-by-span: an endpoint that 404s (embedding
    flavor) or refuses the connection contributes nothing, never an
    error."""
    from kukeon_tpu.runtime import daemon as d

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):
            pass

        def do_GET(self):
            body = json.dumps({"error": "no tracer"}).encode()
            self.send_response(404)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        spans = d.fetch_traces(
            [("a", f"http://127.0.0.1:{srv.server_address[1]}", {}),
             ("b", "http://127.0.0.1:9", {})],       # connection refused
            trace_id="ab" * 16, timeout_s=2.0)
        assert spans == []
    finally:
        srv.shutdown()
        srv.server_close()


# --- kuke trace CLI ----------------------------------------------------------


def test_cmd_trace_renders_timeline(capsys, monkeypatch):
    import argparse

    from kukeon_tpu.runtime import cli

    tid = new_trace_id()
    gsid = new_span_id()
    spans = [
        {"traceId": tid, "spanId": gsid, "component": "gateway",
         "cell": "default/default/default/llm", "requestId": 0,
         "startedAt": 100.0, "outcome": "ok", "e2eS": 0.2,
         "attrs": {"retries": 1, "replica": "r1"},
         "events": [
             {"event": "submitted", "atS": 0.0},
             {"event": "proxy_attempt", "atS": 0.001,
              "attrs": {"replica": "r0"}},
             {"event": "proxy_retry", "atS": 0.002,
              "attrs": {"replica": "r0", "reason": "status_429"}},
             {"event": "proxy_attempt", "atS": 0.003,
              "attrs": {"replica": "r1"}},
             {"event": "finished", "atS": 0.2}],
         "phasesS": {"submitted": 0.2}},
        {"traceId": tid, "spanId": new_span_id(), "parentSpanId": gsid,
         "component": "engine", "cell": "default/default/default/llm/r1",
         "requestId": 7, "startedAt": 100.01, "outcome": "ok",
         "tokens": 3, "e2eS": 0.19, "events": [],
         "phasesS": {"queued": 0.01, "prefill_wait": 0.08, "decode": 0.1}},
    ]

    class _Client:
        def call(self, method, **params):
            assert method == "Traces" and params["traceId"] == tid
            return {"spans": spans}

    monkeypatch.setattr(cli, "_client", lambda args: _Client())
    assert cli.cmd_trace(argparse.Namespace(trace_id=tid, json=False)) == 0
    out = capsys.readouterr().out
    assert f"trace {tid}" in out
    assert "attempts r0!status_429 -> r1" in out
    assert "decode 100.0ms" in out
    # The engine child renders indented under its gateway parent.
    glines = [ln for ln in out.splitlines() if " gateway " in ln]
    elines = [ln for ln in out.splitlines() if " engine " in ln]
    assert glines and elines
    assert (len(elines[0]) - len(elines[0].lstrip())
            > len(glines[0]) - len(glines[0].lstrip()))

    # Unknown trace -> nonzero exit and a clear message.
    class _Empty:
        def call(self, method, **params):
            return {"spans": []}

    monkeypatch.setattr(cli, "_client", lambda args: _Empty())
    assert cli.cmd_trace(argparse.Namespace(trace_id="00" * 16,
                                            json=False)) == 1


# --- exemplars through federation + kuke top ---------------------------------


def test_exemplars_survive_federation_and_reach_top_summary():
    reg = Registry()
    reg.gauge("kukeon_cell_info", "id", labels=("model", "kind")).set(
        1, model="tiny", kind="decoder")
    reg.gauge("kukeon_cell_uptime_seconds", "up").set(10.0)
    h = reg.histogram("kukeon_engine_ttft_seconds", "ttft")
    fast_tid, slow_tid = new_trace_id(), new_trace_id()
    for _ in range(20):
        h.observe(0.001, exemplar=fast_tid)
    h.observe(2.0, exemplar=slow_tid)
    text = expo.render(reg)
    fams = fed.parse(text)
    # Relabel + merge + re-render round-trips the exemplars.
    fed.inject_label(fams, cell="r/s/st/llm")
    merged = fed.merge([fams])
    out = fed.render(merged)
    fams2 = fed.parse(out)
    exs = fams2["kukeon_engine_ttft_seconds"].exemplars
    assert {e[2] for e in exs} == {fast_tid, slow_tid}
    assert all(e[1]["cell"] == "r/s/st/llm" for e in exs)
    # The `kuke top` summary picks the top-bucket exemplar: the slow one.
    from kukeon_tpu.runtime.daemon import summarize_cell_scrape

    row = summarize_cell_scrape(fams2)
    assert row["ttftP95TraceId"] == slow_tid


def test_kuke_top_cell_row_links_p95_exemplar(capsys, monkeypatch):
    import argparse

    from kukeon_tpu.runtime import cli

    tid = new_trace_id()
    rows = [{"cell": "default/default/default/llm", "ok": True,
             "model": "tiny", "ready": True, "qps": 6.2, "queueDepth": 1,
             "ttftP50S": 0.01, "ttftP95S": 0.09, "ttftP95TraceId": tid,
             "phase": "ready", "restarts": 0}]

    class _Client:
        def call(self, method, **params):
            return {"cells": rows}

    monkeypatch.setattr(cli, "_client", lambda args: _Client())
    assert cli.cmd_top(argparse.Namespace(json=False)) == 0
    out = capsys.readouterr().out
    assert f"(p95 trace={tid})" in out


# --- cold-start boot spans ---------------------------------------------------


def test_finish_boot_exports_phases_and_boot_span(real_cell):
    cell, _port = real_cell
    phases = cell.finish_boot()
    assert set(phases) >= {"imports", "init", "compile", "warmup", "serve"}
    assert all(v >= 0 for v in phases.values())
    reg = cell.registry
    total = reg.get("kukeon_cold_start_seconds").value()
    assert total > 0
    # The phases partition the total (same clock, exact by construction).
    assert abs(sum(phases.values()) - total) < 0.5
    g = reg.get("kukeon_cold_start_phase_seconds")
    assert g.value(phase="compile") == phases["compile"]
    # The boot span landed in the trace ring as its own component.
    boot = [s for s in cell.engine.tracer.recent(50)
            if s["component"] == "boot"]
    assert boot
    events = [e["event"] for e in boot[0]["events"]]
    assert {"boot_imports", "boot_init", "boot_compile",
            "boot_warmup"} <= set(events)
    # chip_smoke.py parses these off /metrics.
    fams = fed.parse(expo.render(reg))
    got = {lab["phase"] for _n, lab, _v
           in fams["kukeon_cold_start_phase_seconds"].samples}
    assert {"imports", "init", "compile", "warmup", "serve"} <= got


# --- JSON log correlation ----------------------------------------------------


def test_json_logs_carry_trace_id():
    import io
    import logging

    from kukeon_tpu.runtime import logging_setup

    buf = io.StringIO()
    logging_setup.setup(level="debug", stream=buf, fmt="json")
    try:
        eng = _tiny_engine()
        ctx = obs_trace.TraceContext(trace_id=new_trace_id(),
                                     span_id=new_span_id())
        req = eng.submit(PROMPT, SamplingParams(max_new_tokens=2),
                         trace_ctx=ctx)
        while not req.done.is_set():
            eng.step()
        records = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        done = [r for r in records
                if r.get("request_id") == req.id and "ok" in r.get("msg", "")]
        assert done, records
        assert done[0]["trace_id"] == ctx.trace_id
    finally:
        logging_setup.setup(level="info", stream=None, fmt="text")
        logging.getLogger("kukeon").setLevel(logging.INFO)
