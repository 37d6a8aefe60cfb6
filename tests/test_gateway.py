"""Replica gateway (ISSUE 5): prefix-affinity routing, retry-on-sibling,
ndjson streaming passthrough, rolling restarts with stable chip grants, and
the scrape/CLI/bench surfaces that ride along.

Replica failure is always *scripted* (shed flags, RST injection, abrupt
server close), never timed — the same philosophy as the resilience suite."""

from __future__ import annotations

import http.client
import json
import socket
import struct
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from kukeon_tpu import obs
from kukeon_tpu.gateway.cell import GatewayCell, make_gateway_handler
from kukeon_tpu.gateway.router import (
    POLICY_AFFINITY,
    POLICY_AFFINITY_FALLBACK,
    POLICY_LEAST_LOADED,
    Router,
)
from kukeon_tpu.runtime.api import types as t
from kukeon_tpu.runtime.cells import FakeBackend
from kukeon_tpu.runtime.controller import Controller
from kukeon_tpu.runtime.devices import TPUDeviceManager
from kukeon_tpu.runtime.metadata import MetadataStore
from kukeon_tpu.runtime.runner import Runner, RunnerOptions
from kukeon_tpu.runtime.store import ResourceStore

from test_obs import _parse_expo


# --- fake replica ------------------------------------------------------------


class FakeReplica:
    """A serving cell stand-in speaking exactly the surface the gateway and
    the rollout machinery consume — /v1/generate (+stream), /v1/stats,
    /readyz, /healthz, /drain — with scripted failure modes:

    - ``shed_429``: every generate sheds 429 + Retry-After (queue full)
    - ``stream_script``: exact bytes to emit as the stream body (the
      byte-for-byte passthrough fixtures)
    - ``stream_rst_after``: emit K ndjson lines then RST the connection
      (a replica process dying mid-stream)
    - ``drain``: stops admitting (503), waits out in-flight work, then
      shuts its HTTP server down — like the real cell exiting post-drain.
    """

    def __init__(self, port: int = 0, tokens: int = 3, delay_s: float = 0.0):
        self.tokens = tokens
        self.delay_s = delay_s
        self.ready = True
        self.draining = False
        self.drained = False
        self.queue_depth = 0
        self.shed_429 = False
        self.stream_script: bytes | None = None
        self.stream_rst_after: int | None = None
        self.requests = 0
        self.prefix_ids: list[str | None] = []
        self.inflight = 0
        self._lock = threading.Lock()
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
                if self.path == "/readyz":
                    if outer.ready and not outer.draining:
                        self._json(200, {"ready": True})
                    else:
                        self._json(503, {"ready": False, "reason":
                                         "draining" if outer.draining
                                         else "not ready"})
                elif self.path == "/v1/stats":
                    self._json(200, outer.stats())
                elif self.path in ("/healthz", "/v1/health"):
                    self._json(200, {"status": "ok"})
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if self.path == "/drain":
                    self._json(200, {"draining": True,
                                     "started": outer.begin_drain()})
                    return
                if self.path != "/v1/generate":
                    self._json(404, {"error": f"no route {self.path}"})
                    return
                if outer.draining or not outer.ready:
                    self._json(503, {"error": "not admitting: draining"},
                               {"Retry-After": "1"})
                    return
                if outer.shed_429:
                    self._json(429, {"error": "queue full"},
                               {"Retry-After": "1"})
                    return
                with outer._lock:
                    outer.requests += 1
                    outer.prefix_ids.append(req.get("prefixId"))
                    outer.inflight += 1
                try:
                    if outer.delay_s:
                        time.sleep(outer.delay_s)
                    if req.get("stream"):
                        self._stream()
                        return
                    self._json(200, {"tokens": list(range(outer.tokens)),
                                     "text": "x" * outer.tokens,
                                     "numTokens": outer.tokens,
                                     "seconds": 0.0})
                finally:
                    with outer._lock:
                        outer.inflight -= 1

            def _stream(self):
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.end_headers()
                if outer.stream_script is not None:
                    self.wfile.write(outer.stream_script)
                    self.wfile.flush()
                    return
                for i in range(outer.tokens):
                    if (outer.stream_rst_after is not None
                            and i >= outer.stream_rst_after):
                        # RST, not FIN: a dying process, not a clean close.
                        # The pause lets the gateway relay the flushed
                        # lines first (an RST discards data still sitting
                        # in the receiver's kernel buffer).
                        self.wfile.flush()
                        time.sleep(0.2)
                        self.connection.setsockopt(
                            socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
                        self.connection.close()
                        return
                    self.wfile.write((json.dumps(
                        {"token": i, "text": f"t{i}"}) + "\n").encode())
                    self.wfile.flush()
                self.wfile.write((json.dumps(
                    {"done": True, "numTokens": outer.tokens}) + "\n"
                ).encode())

        self.server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stats(self) -> dict:
        return {"model": "tiny",
                "ready": self.ready and not self.draining,
                "draining": self.draining,
                "queueDepth": self.queue_depth,
                "inflight": self.inflight}

    def begin_drain(self) -> bool:
        if self.draining:
            return False
        self.draining = True

        def _loop():
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and self.inflight:
                time.sleep(0.02)
            self.drained = True
            self.kill()

        threading.Thread(target=_loop, daemon=True).start()
        return True

    def kill(self) -> None:
        """Stop serving (new dials get connection refused)."""
        try:
            self.server.shutdown()
            self.server.server_close()
        except OSError:
            pass


def _gateway(replicas: list[FakeReplica], **kw) -> tuple[GatewayCell, int]:
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("request_timeout_s", 30.0)
    gw = GatewayCell("tiny", [r.url for r in replicas], **kw)
    gw.start()
    srv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    gw.router.poll_once()
    gw._test_server = srv   # keep a handle for teardown
    return gw, srv.server_address[1]


def _teardown(gw: GatewayCell, *replicas: FakeReplica) -> None:
    gw._test_server.shutdown()
    gw._test_server.server_close()
    gw.stop()
    for r in replicas:
        r.kill()


def _post(port: int, path: str, body: dict, timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read()
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, raw, headers


# --- router units ------------------------------------------------------------


def _static_router(n=3) -> Router:
    r = Router([(f"r{i}", f"http://127.0.0.1:{20000 + i}")
                for i in range(n)])
    for rep in r.replicas:
        rep.ready = True
    return r


def test_router_picks_least_loaded():
    r = _static_router()
    r.by_name["r0"].queue_depth = 5
    r.by_name["r1"].queue_depth = 1
    r.by_name["r2"].queue_depth = 3
    rep, policy = r.pick()
    assert (rep.name, policy) == ("r1", POLICY_LEAST_LOADED)
    # Gateway-side inflight breaks the polled tie.
    r.by_name["r1"].queue_depth = 3
    r.by_name["r1"].begin()
    r.by_name["r2"].queue_depth = 3
    rep, _ = r.pick()
    assert rep.name == "r2"


def test_router_affinity_is_stable_and_falls_back():
    r = _static_router()
    picks = {r.pick(prefix_id=f"sess-{i}")[0].name for _ in range(5)
             for i in range(8)}
    # Same prefix always lands on the same replica...
    for i in range(8):
        first = r.pick(prefix_id=f"sess-{i}")
        assert first[1] == POLICY_AFFINITY
        for _ in range(5):
            assert r.pick(prefix_id=f"sess-{i}")[0].name == first[0].name
    assert len(picks) > 1          # ...and 8 sessions spread over >1 replica
    # Unready affine replica: fall back to least-loaded, and the mapping
    # SNAPS BACK once it recovers (rendezvous hashes the full set).
    sess = "sess-0"
    home = r.affine(sess)
    home.ready = False
    rep, policy = r.pick(prefix_id=sess)
    assert policy == POLICY_AFFINITY_FALLBACK and rep.name != home.name
    home.ready = True
    assert r.pick(prefix_id=sess)[0].name == home.name
    # Nothing ready: nothing routable.
    for rep in r.replicas:
        rep.ready = False
    assert r.pick(prefix_id=sess) == (None, None)


# --- gateway proxy -----------------------------------------------------------


def test_gateway_proxies_and_counts_per_replica():
    a, b = FakeReplica(), FakeReplica()
    gw, port = _gateway([a, b])
    try:
        for i in range(6):
            status, raw, _ = _post(port, "/v1/generate",
                                   {"prompt": "hi", "maxNewTokens": 3})
            assert status == 200
            assert json.loads(raw)["numTokens"] == 3
        assert a.requests + b.requests == 6
        # /v1/stats mirrors the routing view; /metrics golden-parses and
        # carries the per-replica families.
        stats_conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        stats_conn.request("GET", "/v1/stats")
        stats = json.loads(stats_conn.getresponse().read())
        stats_conn.close()
        assert stats["kind"] == "gateway"
        assert stats["readyReplicas"] == 2
        assert len(stats["replicas"]) == 2
        mconn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        mconn.request("GET", "/metrics")
        fams = _parse_expo(mconn.getresponse().read().decode())
        mconn.close()
        assert "kukeon_gateway_requests_total" in fams
        ready = {lab["replica"]: float(v) for _n, lab, v
                 in fams["kukeon_gateway_replica_ready"]["samples"]}
        assert ready == {"r0": 1.0, "r1": 1.0}
    finally:
        _teardown(gw, a, b)


def test_gateway_readyz_and_healthz():
    a = FakeReplica()
    gw, port = _gateway([a])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/readyz")
        assert conn.getresponse().status == 200
        conn.close()
        a.ready = False
        gw.router.poll_once()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        assert resp.status == 503
        conn.close()
        # Liveness never depends on the replicas.
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 200
        conn.close()
    finally:
        _teardown(gw, a)


def test_prefix_affinity_sticks_through_the_gateway():
    """Acceptance: each prefix_id lands on exactly ONE replica (per-replica
    request counters), and the gateway's choice matches the router policy."""
    a, b = FakeReplica(), FakeReplica()
    gw, port = _gateway([a, b])
    try:
        prefixes = [f"agent-{i}" for i in range(8)]
        for _round in range(3):
            for p in prefixes:
                status, _raw, _ = _post(port, "/v1/generate",
                                        {"prompt": "x", "prefixId": p})
                assert status == 200
        by_replica = {"r0": set(a.prefix_ids), "r1": set(b.prefix_ids)}
        for p in prefixes:
            seen = [name for name, ids in by_replica.items() if p in ids]
            assert len(seen) == 1, f"{p} split across replicas: {seen}"
            assert seen[0] == gw.router.affine(p).name
        # 8 sessions spread over both replicas (deterministic hash).
        assert a.prefix_ids and b.prefix_ids
        fams = _parse_expo(obs.expo.render(gw.registry))
        routing = {lab["policy"]: float(v) for _n, lab, v
                   in fams["kukeon_gateway_routing_total"]["samples"]}
        assert routing.get("affinity") == 24.0
    finally:
        _teardown(gw, a, b)


def test_retry_on_shedding_replica_then_passthrough_when_all_shed():
    # spill_capacity=0: this test pins the PASSTHROUGH contract (what an
    # all-shed storm degrades to when the spillover queue is full); the
    # spillover queue itself is covered in tests/test_scaler.py.
    a, b = FakeReplica(), FakeReplica()
    gw, port = _gateway([a, b], spill_capacity=0)
    try:
        # Aim at a prefix whose home is r0, then make r0 shed.
        sess = next(p for p in (f"s{i}" for i in range(64))
                    if gw.router.affine(p).name == "r0")
        a.shed_429 = True
        status, raw, _ = _post(port, "/v1/generate",
                               {"prompt": "x", "prefixId": sess})
        assert status == 200                    # retried onto r1
        assert b.requests == 1 and a.requests == 0
        assert gw.registry.get("kukeon_gateway_retries_total").value(
            reason="status_429") == 1
        assert gw.registry.get("kukeon_gateway_requests_total").value(
            replica="r0", outcome="shed") == 1
        # Both shedding: the last replica's 429 passes through, with
        # Retry-After intact, so the client backs off instead of erroring.
        b.shed_429 = True
        status, raw, headers = _post(port, "/v1/generate", {"prompt": "x"})
        assert status == 429
        assert "Retry-After" in headers
        assert "queue full" in json.loads(raw)["error"]
    finally:
        _teardown(gw, a, b)


def test_draining_replica_leaves_rotation_and_503_retries():
    a, b = FakeReplica(), FakeReplica()
    gw, port = _gateway([a, b])
    try:
        sess = next(p for p in (f"s{i}" for i in range(64))
                    if gw.router.affine(p).name == "r0")
        # The replica turns draining BETWEEN polls: the gateway's first
        # contact is the 503, which must demote + retry transparently.
        a.draining = True
        status, _raw, _ = _post(port, "/v1/generate",
                                {"prompt": "x", "prefixId": sess})
        assert status == 200
        assert b.requests == 1
        assert gw.registry.get("kukeon_gateway_retries_total").value(
            reason="status_503") == 1
        assert not gw.router.by_name["r0"].ready   # demoted on the spot
    finally:
        _teardown(gw, a, b)


def test_no_replica_available_sheds_503_with_retry_after():
    # spill_capacity=0 pins the terminal 503 shape (see the spillover
    # suite in tests/test_scaler.py for the parking behavior).
    a, b = FakeReplica(), FakeReplica()
    gw, port = _gateway([a, b], spill_capacity=0)
    try:
        a.ready = False
        b.ready = False
        gw.router.poll_once()
        status, raw, headers = _post(port, "/v1/generate", {"prompt": "x"})
        assert status == 503
        assert "Retry-After" in headers
        assert gw.registry.get("kukeon_gateway_shed_total").value() == 1
        assert a.requests == b.requests == 0
    finally:
        _teardown(gw, a, b)


# --- streaming passthrough (PR-1 fixtures through the proxy) -----------------


def test_stream_passthrough_is_byte_exact():
    """The two PR-1 streaming invariants must survive the proxy BYTE FOR
    BYTE: raw multi-byte UTF-8 in a delta (the split-codepoint holdback
    shape) and an in-band terminal {"error": ...} line."""
    script = ('{"token": 104, "text": "h"}\n'
              '{"token": 195, "text": ""}\n'
              '{"token": 169, "text": "é"}\n'
              '{"token": 33, "text": "!"}\n'
              '{"error": "RuntimeError: device lost mid-stream"}\n'
              ).encode()
    a = FakeReplica()
    a.stream_script = script
    gw, port = _gateway([a])
    try:
        status, raw, headers = _post(port, "/v1/generate",
                                     {"prompt": "x", "stream": True})
        assert status == 200
        assert headers["Content-Type"] == "application/x-ndjson"
        assert raw == script
        # A script WITHOUT a trailing newline is also untouched (the
        # gateway only ever appends on a mid-stream failure).
        a.stream_script = b'{"token": 1, "text": "a"}\n{"done": true}'
        _status, raw, _ = _post(port, "/v1/generate",
                                {"prompt": "x", "stream": True})
        assert raw == a.stream_script
    finally:
        _teardown(gw, a)


def test_stream_through_gateway_from_real_cell_holds_back_split_utf8():
    """End-to-end with the REAL serving cell streaming machinery (the PR-1
    split-codepoint fixture): deltas that cross the gateway must join to
    the exact final text with no U+FFFD ever on the wire."""
    from http.server import ThreadingHTTPServer as HS

    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    cell = ServingCell("tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None)
    script = [0x68] + list("é".encode()) + [0x21]     # "h", é split, "!"

    class FakeReq:
        def __init__(self):
            self.done = threading.Event()
            self.error = None
            self.cancelled = False
            self.timed_out = False
            self.trace = None

        def cancel(self):
            self.cancelled = True

    class FakeEngine:
        # The cell's /v1/stats (which the gateway polls for routing) reads
        # these engine fields; keep the surface the real engine presents.
        _running = True
        _requests: dict = {}
        prefix_hits = 0
        prefix_misses = 0
        _prefix_cache: dict = {}
        decode_chunk = 4
        kv_cache_int8 = False
        page_tokens = 0
        kv_pool_pages = 0
        _pool = None
        tune = None
        max_pending = None
        shed_stats = {"rejected": 0, "timed_out": 0, "kv_exhausted": 0}

        def submit(self, prompt, sp, emit=None, prefix_id=None,
                   deadline_s=None, trace_ctx=None):
            r = FakeReq()
            for i, tok in enumerate(script):
                emit(tok, i == len(script) - 1)
            r.done.set()
            return r

    cell.engine = FakeEngine()
    cell.mark_ready()
    srv = HS(("127.0.0.1", 0), make_handler(cell))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    rep_url = f"http://127.0.0.1:{srv.server_address[1]}"
    gw = GatewayCell("tiny", [rep_url], poll_interval_s=0.05)
    gw.start()
    gsrv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=gsrv.serve_forever, daemon=True).start()
    gw.router.poll_once()
    try:
        status, raw, _ = _post(gsrv.server_address[1], "/v1/generate",
                               {"prompt": "x", "maxNewTokens": 8,
                                "stream": True})
        assert status == 200
        lines = [json.loads(x) for x in raw.decode().splitlines()]
        deltas = [r["text"] for r in lines[:-1]]
        assert deltas == ["h", "", "é", "!"]
        assert "".join(deltas) == "hé!" == lines[-1]["text"]
        assert not any("�" in d for d in deltas)
    finally:
        gsrv.shutdown()
        gsrv.server_close()
        gw.stop()
        srv.shutdown()
        srv.server_close()


def test_midstream_replica_death_surfaces_in_band():
    """A replica dying mid-stream (RST) must produce an in-band terminal
    error line — never a retry (bytes already reached the client), never a
    second status line, never a hang."""
    a = FakeReplica(tokens=6)
    a.stream_rst_after = 2
    gw, port = _gateway([a])
    try:
        status, raw, _ = _post(port, "/v1/generate",
                               {"prompt": "x", "stream": True})
        assert status == 200
        assert b"HTTP/" not in raw
        lines = [json.loads(x) for x in raw.decode().splitlines()]
        assert lines[0] == {"token": 0, "text": "t0"}
        assert lines[1] == {"token": 1, "text": "t1"}
        assert "replica failed mid-stream" in lines[-1]["error"]
        assert a.requests == 1            # no second replica, no retry
        assert gw.registry.get("kukeon_gateway_requests_total").value(
            replica="r0", outcome="stream_error") == 1
    finally:
        _teardown(gw, a)


# --- acceptance: kill a replica mid-flood ------------------------------------


def test_kill_replica_mid_flood_yields_only_429_or_in_band():
    """Acceptance: 2 replicas under flood, one killed mid-flood — every
    non-stream response is 200/429 (no 500s, no gateway mystery codes), no
    request hangs, and the survivor absorbs the traffic."""
    a, b = FakeReplica(delay_s=0.005), FakeReplica(delay_s=0.005)
    gw, port = _gateway([a, b])
    statuses: list[int] = []
    errors: list[str] = []
    lock = threading.Lock()
    stop = threading.Event()

    def flood(i: int):
        while not stop.is_set():
            try:
                status, _raw, _ = _post(port, "/v1/generate",
                                        {"prompt": "x",
                                         "prefixId": f"sess-{i}"},
                                        timeout=30)
                with lock:
                    statuses.append(status)
            except Exception as e:  # noqa: BLE001 — a transport error is a failure
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

    try:
        threads = [threading.Thread(target=flood, args=(i,))
                   for i in range(6)]
        for th in threads:
            th.start()
        time.sleep(0.3)
        a.kill()                          # one replica dies mid-flood
        time.sleep(0.6)
        stop.set()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads), "flood thread hung"
        assert not errors, errors
        assert statuses, "flood produced no responses"
        bad = [s for s in statuses if s not in (200, 429)]
        assert not bad, f"non-200/429 statuses: {sorted(set(bad))}"
        # The survivor actually took traffic after the kill.
        assert b.requests > 0
    finally:
        stop.set()
        _teardown(gw, a, b)


# --- rolling restart ---------------------------------------------------------


@pytest.fixture
def replicated_ctl(tmp_path):
    """Controller (fake backend, 4 chips) — the chip/lifecycle half of the
    rollout story; HTTP replicas ride separately per test."""
    store = ResourceStore(MetadataStore(str(tmp_path)))
    backend = FakeBackend()
    devices = TPUDeviceManager(store.ms, chips=[0, 1, 2, 3])
    runner = Runner(store, backend, cgroups=None, devices=devices,
                    options=RunnerOptions(stop_grace_s=0.2),
                    registry=obs.Registry())
    ctl = Controller(store, runner)
    ctl.bootstrap()
    return ctl, backend, store, devices


def _free_port_block(n: int) -> int:
    """Base of n consecutive free TCP ports (the replicated ModelSpec's
    port..port+n layout needs real contiguous ports in these tests)."""
    for _attempt in range(50):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
        s.close()
        probes = []
        try:
            for p in range(base, base + n):
                x = socket.socket()
                x.bind(("127.0.0.1", p))
                probes.append(x)
            return base
        except OSError:
            continue
        finally:
            for x in probes:
                x.close()
    raise RuntimeError("no contiguous port block found")


def test_runner_materializes_replicas_and_gateway(replicated_ctl):
    ctl, backend, store, devices = replicated_ctl
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1,
                                          replicas=2, port=9300)),
    )
    ctl.create_cell(doc)
    started = {c.spec.name: c for c in backend.started}
    assert set(started) == {"model-server-0", "model-server-1", "gateway"}
    # Base-port scheme: replicas above the base, gateway ON the base.
    assert "9301" in " ".join(started["model-server-0"].command)
    assert "9302" in " ".join(started["model-server-1"].command)
    gcmd = started["gateway"].command
    assert "kukeon_tpu.gateway.cell" in " ".join(gcmd)
    assert gcmd[gcmd.index("--port") + 1] == "9300"
    assert [u for f, u in zip(gcmd, gcmd[1:]) if f == "--replica"] == [
        "http://127.0.0.1:9301", "http://127.0.0.1:9302"]
    # Chips partition deterministically; the gateway gets none.
    assert started["model-server-0"].env["TPU_VISIBLE_DEVICES"] == "0"
    assert started["model-server-1"].env["TPU_VISIBLE_DEVICES"] == "1"
    assert "TPU_VISIBLE_DEVICES" not in started["gateway"].env
    rec = store.read_cell("default", "default", "default", "llm")
    assert rec.status.tpu_chips == [0, 1]


def test_runner_materializes_disagg_roles(replicated_ctl):
    """`role: "prefill,decode"` assigns one role atom per replica in
    declaration order (the same order the base-port scheme assigns ports);
    the gateway container gets NO role flags — it discovers pools from
    each cell's /v1/stats census."""
    ctl, backend, _store, _devices = replicated_ctl
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1,
                                          replicas=2, port=9300,
                                          role="prefill,decode")),
    )
    ctl.create_cell(doc)
    started = {c.spec.name: c for c in backend.started}
    cmd0 = started["model-server-0"].command
    cmd1 = started["model-server-1"].command
    assert cmd0[cmd0.index("--role") + 1] == "prefill"
    assert cmd1[cmd1.index("--role") + 1] == "decode"
    assert "--role" not in started["gateway"].command
    # The mixed default stays flag-free: byte-identical to before roles.
    from kukeon_tpu.runtime.api.types import ModelSpec
    from kukeon_tpu.runtime.runner import Runner  # noqa: F401 — ctl.runner

    for c in ctl.runner._model_containers(
            ModelSpec(model="tiny", chips=1, replicas=2, port=9400)):
        assert "--role" not in c.command


def test_rolling_restart_under_flood_zero_failures(replicated_ctl,
                                                   monkeypatch):
    """Acceptance + satellite: flood the gateway while RolloutCell rolls
    both replicas; zero non-429 failures, and every replica comes back on
    its exact chip grant."""
    from kukeon_tpu.runtime import daemon as dmod

    ctl, backend, store, devices = replicated_ctl
    base = _free_port_block(3)
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1,
                                          replicas=2, port=base)),
    )
    ctl.create_cell(doc)

    replicas = {0: FakeReplica(port=base + 1, delay_s=0.003),
                1: FakeReplica(port=base + 2, delay_s=0.003)}
    gw, gport = _gateway([replicas[0], replicas[1]])

    grants: dict[str, list[str]] = {}
    real_restart = dmod._rollout_restart

    def restart_and_respawn(ctl_, rec, cname):
        i = int(cname.rsplit("-", 1)[1])
        # The drained fake shut its server down (kill() is the idempotent
        # backstop — wait_drained can win the race against the drain
        # loop's own shutdown, and the port must be free before respawn);
        # a real drained cell exits 0 — mirror that in the fake backend
        # before the runner restart.
        replicas[i].kill()
        cdir = store.container_dir(rec.realm, rec.space, rec.stack,
                                   rec.name, cname)
        backend.exit(cdir, 0)
        real_restart(ctl_, rec, cname)
        grants.setdefault(cname, []).append(
            backend.started[-1].env["TPU_VISIBLE_DEVICES"])
        replicas[i] = FakeReplica(port=base + 1 + i, delay_s=0.003)

    monkeypatch.setattr(dmod, "_rollout_restart", restart_and_respawn)
    service = dmod.RPCService(ctl)

    statuses: list[int] = []
    errors: list[str] = []
    lock = threading.Lock()
    stop = threading.Event()

    def flood(i: int):
        while not stop.is_set():
            try:
                status, _raw, _ = _post(gport, "/v1/generate",
                                        {"prompt": "x",
                                         "prefixId": f"sess-{i}"},
                                        timeout=30)
                with lock:
                    statuses.append(status)
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=flood, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    try:
        out = service.RolloutCell("default", "default", "default", "llm",
                                  drainTimeoutS=15.0, readyTimeoutS=15.0)
    finally:
        time.sleep(0.2)
        stop.set()
        for th in threads:
            th.join(timeout=60)
        _teardown(gw, *replicas.values())
    assert not any(th.is_alive() for th in threads), "flood thread hung"

    # The rollout touched both replicas, in order, and reported readiness.
    assert [r["replica"] for r in out["replicas"]] == [
        "model-server-0", "model-server-1"]
    assert all(r["drained"] for r in out["replicas"])
    # Zero failed requests: every response 200 (or an honest 429 shed).
    assert not errors, errors
    assert statuses, "flood produced no responses"
    bad = [s for s in statuses if s not in (200, 429)]
    assert not bad, f"non-200/429 statuses during rollout: {sorted(set(bad))}"
    # Each replica came back on ITS chip grant.
    assert grants == {"model-server-0": ["0"], "model-server-1": ["1"]}
    rec = store.read_cell("default", "default", "default", "llm")
    assert rec.status.tpu_chips == [0, 1]
    assert rec.status.container("model-server-0").restarts == 1
    assert rec.status.container("model-server-1").restarts == 1


def test_rollout_rejects_unreplicated_cell(replicated_ctl):
    from kukeon_tpu.runtime import daemon as dmod
    from kukeon_tpu.runtime.errors import FailedPrecondition

    ctl, _backend, _store, _devices = replicated_ctl
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="solo"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1, port=9400)),
    )
    ctl.create_cell(doc)
    service = dmod.RPCService(ctl)
    with pytest.raises(FailedPrecondition, match="replicas"):
        service.RolloutCell("default", "default", "default", "solo")


def test_rolling_restart_aborts_when_replica_never_ready():
    from kukeon_tpu.gateway import RolloutError, RolloutStep, rolling_restart

    a = FakeReplica()
    step = RolloutStep(name="model-server-0", url=a.url,
                       restart=lambda: None)    # nothing comes back up
    with pytest.raises(RolloutError, match="did not become ready"):
        rolling_restart([step], drain_timeout_s=3.0, ready_timeout_s=0.5,
                        poll_s=0.05)


# --- federation / scrape / CLI surfaces --------------------------------------


def test_model_cell_endpoints_cover_gateway_and_replicas(replicated_ctl):
    from kukeon_tpu.runtime.daemon import model_cell_endpoints

    ctl, _backend, _store, _devices = replicated_ctl
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1,
                                          replicas=2, port=9300)),
    )
    ctl.create_cell(doc)
    eps = {key: url for key, url, _rec in model_cell_endpoints(ctl)}
    assert eps == {
        "default/default/default/llm": "http://127.0.0.1:9300",
        "default/default/default/llm/r0": "http://127.0.0.1:9301",
        "default/default/default/llm/r1": "http://127.0.0.1:9302",
    }


def test_scrape_cells_renders_gateway_row(replicated_ctl):
    """ScrapeCells summarizes a gateway endpoint with aggregate QPS,
    retries, and the replica-ready census; the (dead here) replica rows
    still appear instead of silently vanishing."""
    from kukeon_tpu.runtime import daemon as dmod

    ctl, _backend, _store, _devices = replicated_ctl
    live = FakeReplica()
    gw = GatewayCell("tiny", [live.url, "http://127.0.0.1:9"],
                     poll_interval_s=0.05)
    gsrv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=gsrv.serve_forever, daemon=True).start()
    gw.router.poll_once()
    gport = gsrv.server_address[1]
    # A couple of proxied requests so QPS/retry counters are non-trivial.
    for _ in range(3):
        assert _post(gport, "/v1/generate", {"prompt": "x"})[0] == 200
    doc = t.Document(
        kind=t.KIND_CELL, metadata=t.Metadata(name="llm"),
        spec=t.CellSpec(model=t.ModelSpec(model="tiny", chips=1,
                                          replicas=2, port=gport)),
    )
    ctl.create_cell(doc)
    service = dmod.RPCService(ctl)
    try:
        rows = {r["cell"]: r for r in service.ScrapeCells()["cells"]}
        g = rows["default/default/default/llm"]
        assert g["ok"] and g["kind"] == "gateway"
        assert g["model"] == "tiny"
        assert g["replicas"] == 2 and g["readyReplicas"] == 1
        assert g["ready"] is True
        assert g["qps"] is not None and g["qps"] > 0
        assert "retries" in g
        # Replica rows ride along (down in this fixture, visibly so).
        assert "default/default/default/llm/r0" in rows
        assert "default/default/default/llm/r1" in rows
    finally:
        gsrv.shutdown()
        gsrv.server_close()
        gw.stop()
        live.kill()


def test_kuke_top_renders_gateway_row(capsys, monkeypatch):
    import argparse

    from kukeon_tpu.runtime import cli

    rows = [
        {"cell": "default/default/default/llm", "ok": True,
         "kind": "gateway", "model": "tiny", "qps": 12.5, "retries": 3,
         "readyReplicas": 2, "replicas": 2, "ready": True,
         "phase": "ready", "restarts": 0},
        {"cell": "default/default/default/llm/r0", "ok": True,
         "model": "tiny", "ready": True, "qps": 6.2, "queueDepth": 1,
         "phase": "ready", "restarts": 0},
    ]

    class _Client:
        def call(self, method, **params):
            assert method == "ScrapeCells"
            return {"cells": rows}

    monkeypatch.setattr(cli, "_client", lambda args: _Client())
    assert cli.cmd_top(argparse.Namespace(json=False)) == 0
    out = capsys.readouterr().out
    assert "2/2" in out
    assert "gateway, retries=3" in out
    assert "default/default/default/llm/r0" in out


def test_cmd_rollout_prints_replica_progress(capsys, monkeypatch):
    import argparse

    from kukeon_tpu.runtime import cli

    class _Client:
        def call(self, method, **params):
            assert method == "RolloutCell"
            assert params["name"] == "llm"
            return {"cell": "default/default/default/llm",
                    "replicas": [
                        {"replica": "model-server-0", "drained": True,
                         "readyS": 0.4},
                        {"replica": "model-server-1", "drained": True,
                         "readyS": 0.5},
                    ]}

    monkeypatch.setattr(cli, "_client", lambda args: _Client())
    args = argparse.Namespace(name="llm", json=False, realm=None, space=None,
                              stack=None, drain_timeout=60.0,
                              ready_timeout=300.0)
    assert cli.cmd_rollout(args) == 0
    out = capsys.readouterr().out
    assert "model-server-0" in out and "model-server-1" in out
    assert "rollout complete (2 replicas)" in out
