"""Gateway cell: the HTTP front-end over N serving replicas.

Entrypoint the runner materializes for a replicated ``ModelSpec``
(``python -m kukeon_tpu.gateway.cell --port P --replica URL ...``). One
process, no chips, stateless except for routing state — a crashed gateway
restarts in milliseconds under the runner's restart policy while the
replicas keep their engines warm.

Routes:

  GET  /healthz      -> liveness
  GET  /readyz       -> 200 while >=1 replica is ready (503 otherwise)
  GET  /v1/stats     -> gateway counters + per-replica routing snapshot
  GET  /metrics      -> Prometheus exposition (kukeon_gateway_* families)
  GET  /v1/trace     -> gateway-side proxy spans (replica attempts, retry
                        hops, shed outcomes); ?trace_id= / ?request_id=
                        filters, same surface as the serving cells
  POST /v1/generate  -> proxied to a replica; ``"stream": true`` bodies are
                        passed through byte-for-byte as ndjson
  POST /v1/embed     -> proxied (no affinity; embeddings are stateless)

Retry contract: a replica answering 429/503, or refusing the connection,
triggers a bounded retry on another replica (each replica tried at most
once per request). NEVER for mid-stream failures — by then bytes are on
the client's wire, so the failure surfaces as the in-band terminal
``{"error": ...}`` ndjson line the serving cell already speaks.

Spillover: when EVERY replica shed (or nothing was routable), the request
parks in a bounded deadline-aware queue and retries as replicas free —
a brief all-shed storm becomes latency, not client-visible 429s. Past the
request's deadline the gateway answers the in-band timeout terminal; a
full spill queue (or the armed ``gateway.spill`` fault point) degrades to
the old contract — the last replica's 429/503 passes through (with its
Retry-After), and nothing-reachable sheds 503.
"""

from __future__ import annotations

import argparse
import http.client
import itertools
import json
import math
import sys
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from kukeon_tpu import faults, sanitize
from kukeon_tpu.obs import Registry, Tracer, expo
from kukeon_tpu.obs import trace as obs_trace
from kukeon_tpu.gateway.router import Router

# Retry-After the gateway itself sheds with (no replica routable). Short:
# replicas blip for poll-interval-sized windows, not minutes.
GATEWAY_RETRY_AFTER_S = 2.0
STREAM_CHUNK = 65536

# Spillover defaults: how many all-shed requests may park at the gateway
# (past it the original 429/503 passes through — the queue is a shock
# absorber, not an unbounded backlog) and the longest a request without
# its own deadlineS waits before the in-band timeout terminal.
SPILL_CAPACITY = 64
SPILL_MAX_WAIT_S = 10.0
# Parked requests retry on every router-poll wakeup; this timed wait is
# the backstop cadence when no poll lands (and the loop's deadline check).
SPILL_WAIT_TICK_S = 0.05


class GatewayCell:
    """Routing + proxy brain behind the HTTP handler (handler-free so tests
    can drive it in-process)."""

    def __init__(self, model: str, replica_urls: list[str], *,
                 registry: Registry | None = None,
                 poll_interval_s: float = 0.5,
                 poll_timeout_s: float = 1.0,
                 request_timeout_s: float = 120.0,
                 trace_capacity: int = 512,
                 spill_capacity: int = SPILL_CAPACITY,
                 spill_max_wait_s: float = SPILL_MAX_WAIT_S):
        self.model_name = model
        self.request_timeout_s = request_timeout_s
        self.router = Router(
            [(f"r{i}", u) for i, u in enumerate(replica_urls)],
            poll_interval_s=poll_interval_s, poll_timeout_s=poll_timeout_s)
        self.started_at = time.time()
        # Spillover: an all-shed request parks here (bounded, deadline-
        # aware) instead of handing the client the 429 — see spill_or_shed.
        self.spill_capacity = spill_capacity
        self.spill_max_wait_s = spill_max_wait_s
        self._spill_lock = sanitize.lock("GatewayCell._spill_lock")
        self._spill_cond = sanitize.condition(
            self._spill_lock, name="GatewayCell._spill_cond")
        self._spill_depth = 0   # guarded-by: _spill_lock
        self.router.add_poll_listener(self._spill_wake)
        # Distributed tracing: the gateway is where a request's trace is
        # born (or joined, when the client already carries a traceparent).
        # Its proxy span records every replica attempt + retry hop and
        # lands in this ring behind GET /v1/trace — the gateway-side half
        # of the federated timeline `kuke trace` reconstructs. request_id
        # here is a gateway-local sequence (the engine-side id is minted
        # by whichever replica wins the request).
        self.tracer = Tracer(capacity=trace_capacity)
        self._span_seq = itertools.count()

        reg = registry if registry is not None else Registry()
        self.registry = reg
        reg.gauge("kukeon_gateway_info",
                  "Static gateway identity (value always 1).",
                  labels=("model",)).set(1, model=model)
        reg.gauge("kukeon_gateway_uptime_seconds",
                  "Seconds since gateway construction.").set_function(
            lambda: time.time() - self.started_at)
        reg.gauge("kukeon_gateway_replicas",
                  "Replicas configured behind this gateway.").set(
            len(replica_urls))
        reg.gauge("kukeon_gateway_ready",
                  "1 while at least one replica is ready.").set_function(
            lambda: 1.0 if self.router.ready_count() else 0.0)
        self._m_requests = reg.counter(
            "kukeon_gateway_requests_total",
            "Proxied requests by replica and outcome.",
            labels=("replica", "outcome"))
        self._m_retries = reg.counter(
            "kukeon_gateway_retries_total",
            "Retry-on-another-replica events by reason.",
            labels=("reason",))
        self._m_shed = reg.counter(
            "kukeon_gateway_shed_total",
            "Requests shed at the gateway (no routable replica).")
        self._m_routing = reg.counter(
            "kukeon_gateway_routing_total",
            "Routing decisions by policy.", labels=("policy",))
        # Disaggregated-serving KV handoff telemetry: the gateway drives
        # the prefill-export -> decode-import hop, so the cost of moving a
        # request's KV between cells is measured HERE, where both halves
        # are visible. Families are declared unconditionally so a mixed
        # deployment scrapes stable zeros.
        self._m_handoff_pages = reg.counter(
            "kukeon_handoff_pages_total",
            "KV pages moved prefill->decode across completed handoffs "
            "(1/handoff when the exporter runs the contiguous layout).")
        self._m_handoff_bytes = reg.counter(
            "kukeon_handoff_bytes_total",
            "Serialized KV bytes moved prefill->decode.")
        self._m_handoff_seconds = reg.histogram(
            "kukeon_handoff_seconds",
            "Wall time of one KV handoff: export POST through import "
            "response headers (prefill compute + both transfer legs).")
        self._m_handoff_failures = reg.counter(
            "kukeon_handoff_failures_total",
            "Handoff stage failures (connect error / 5xx / exhausted "
            "retries), by stage.", labels=("stage",))
        self._m_handoff_fallback = reg.counter(
            "kukeon_handoff_fallback_total",
            "Requests that degraded to single-cell local decode after a "
            "handoff stage failed (the graceful path — client still gets "
            "200).")
        self._m_spill = reg.counter(
            "kukeon_gateway_spill_total",
            "All-shed requests parked in the gateway spillover queue, by "
            "final outcome (recovered = a retry won a replica; timeout = "
            "in-band deadline terminal; overflow = queue full, original "
            "shed passed through; fault = gateway.spill chaos seam "
            "degraded the path).", labels=("outcome",))
        for outcome in ("recovered", "timeout", "overflow", "fault"):
            # Declared at 0 so a quiet gateway scrapes a stable schema.
            self._m_spill.inc(0, outcome=outcome)
        reg.gauge(
            "kukeon_gateway_spill_queue_depth",
            "Requests currently parked in the spillover queue."
        ).set_function(lambda: float(self._spill_depth))
        self._m_spill_wait = reg.histogram(
            "kukeon_gateway_spill_wait_seconds",
            "Time a spilled request spent parked before its outcome "
            "(recovered, timeout, or a terminal shed).")
        ready_g = reg.gauge("kukeon_gateway_replica_ready",
                            "1 while the replica is in rotation.",
                            labels=("replica",))
        depth_g = reg.gauge("kukeon_gateway_replica_queue_depth",
                            "Last polled engine queue depth.",
                            labels=("replica",))
        for rep in self.router.replicas:
            ready_g.set_function(
                lambda r=rep: 1.0 if r.ready else 0.0, replica=rep.name)
            depth_g.set_function(
                lambda r=rep: float(r.queue_depth), replica=rep.name)
        reg.register_collector(self._trace_collect)

    def _trace_collect(self):
        ss = self.tracer.sample_stats
        yield ("kukeon_trace_tail_sampled_total", "counter",
               "Tail-sampler verdicts on finished trace spans (error/"
               "preempted/retried/slow spans are always kept).",
               [({"decision": "kept"}, float(ss["kept"])),
                ({"decision": "dropped"}, float(ss["dropped"]))])

    def start(self) -> None:
        self.router.start()

    def stop(self) -> None:
        self.router.stop()

    # --- distributed tracing ----------------------------------------------

    def begin_span(self, route: str,
                   ctx: "obs_trace.TraceContext | None"):
        """The gateway-side proxy span for one request: joins the client's
        trace when a traceparent came in, else roots a fresh one. Every
        replica attempt/retry is recorded on it; downstream hops hang
        under it via the propagated header."""
        span = self.tracer.begin(next(self._span_seq), 0, trace_ctx=ctx,
                                 component="gateway")
        span.attrs["route"] = route
        return span

    def finish_span(self, span, outcome: str, **attrs) -> None:
        if span is None:
            return
        span.attrs.update({k: v for k, v in attrs.items() if v is not None})
        self.tracer.finish(span, outcome)

    # --- proxy plumbing ----------------------------------------------------

    def _open(self, rep, path: str, body: bytes,
              headers: dict[str, str] | None = None):
        """One upstream POST; returns (conn, resp). Caller owns closing."""
        u = urlsplit(rep.url)
        conn = http.client.HTTPConnection(u.hostname, u.port,
                                          timeout=self.request_timeout_s)
        try:
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "application/json",
                                  "Content-Length": str(len(body)),
                                  **(headers or {})})
            return conn, conn.getresponse()
        except Exception:
            conn.close()
            raise

    def _try_replica(self, rep, path: str, body: bytes,
                     fwd_headers: "dict[str, str] | None", span=None,
                     stage: str | None = None):
        """One dial of one replica with the shared demotion/retry
        accounting (connect error and 429/503 are retryable — demote,
        count, record the hop on the span). Returns
        ``("response", conn, resp)`` for anything else (the caller owns
        closing and ``rep.end()``), or ``("retry", last_tuple)`` with
        everything already closed."""
        stage_attrs = {"stage": stage} if stage else {}
        rep.begin()
        try:
            conn, resp = self._open(rep, path, body, fwd_headers)
        except OSError as e:
            rep.end()
            self.router.mark_unready(rep)
            self._m_requests.inc(replica=rep.name, outcome="connect_error")
            self._m_retries.inc(reason="connect_error")
            if span is not None:
                span.event("proxy_retry", replica=rep.name,
                           reason="connect_error", **stage_attrs)
                span.attrs["retries"] = (
                    span.attrs.get("retries", 0) + 1)
            return ("retry", (rep.name, None, str(e), None))
        if resp.status in (429, 503):
            payload = resp.read()
            retry_after = resp.getheader("Retry-After")
            conn.close()
            rep.end()
            if resp.status == 503:
                # Lifecycle refusal (draining / warming / wedged): out
                # of rotation until a poll says otherwise. 429 is queue
                # pressure — the replica stays routable for others.
                self.router.mark_unready(rep)
            self._m_requests.inc(
                replica=rep.name,
                outcome="shed" if resp.status == 429 else "unready")
            self._m_retries.inc(reason=f"status_{resp.status}")
            if span is not None:
                span.event("proxy_retry", replica=rep.name,
                           reason=f"status_{resp.status}", **stage_attrs)
                span.attrs["retries"] = (
                    span.attrs.get("retries", 0) + 1)
            return ("retry", (rep.name, resp.status, payload, retry_after))
        return ("response", conn, resp)

    def select_and_proxy(self, path: str, body: bytes,
                         prefix_id: str | None, span=None,
                         pool: str | None = None,
                         exclude: "set[str] | None" = None):
        """Route with bounded retry until a replica yields a non-retryable
        response. Returns one of:

          ("response", replica, conn, resp)  — pass this response through
          ("shed", status, payload, retry_after_s) — gateway-level answer

        A 2xx "response" may still be a stream the caller relays; the
        replica's inflight counter was incremented via ``rep.begin()`` and
        the caller must ``rep.end()`` when done with the response.

        ``pool`` restricts routing to a role pool (the handoff fallback
        routes over prefill-capable replicas); ``exclude`` seeds the
        per-replica once-per-request set with replicas an earlier handoff
        stage already burned, so the fallback never re-dials a replica
        this request has seen fail.
        """
        excluded: set[str] = set(exclude or ())
        last: tuple | None = None   # (replica_name, status, body, retry_after)
        repolled = False
        attempts = 0
        # Downstream hops join the gateway's trace as children of ITS span
        # (one header for every attempt of this request — the engine-side
        # spans of a retried request share one parent).
        fwd_headers = (
            {obs_trace.TRACEPARENT_HEADER: obs_trace.format_traceparent(
                span.trace_id, span.span_id)}
            if span is not None else None)
        while attempts < max(1, len(self.router.replicas)):
            rep, policy = self.router.pick(prefix_id, exclude=excluded,
                                           pool=pool)
            if rep is None:
                if not repolled:
                    # The routable set can look empty for one poll interval
                    # after a replica comes back (a rollout advances the
                    # moment /readyz flips, faster than the poll tick).
                    # Refresh the snapshot once before shedding — this is
                    # the difference between a zero-failed-request rollout
                    # and a sub-second 503 blip per replica.
                    repolled = True
                    self.router.poll_once()
                    continue
                break
            attempts += 1
            self._m_routing.inc(policy=policy)
            if span is not None:
                span.event("proxy_attempt", replica=rep.name, policy=policy)
            got = self._try_replica(rep, path, body, fwd_headers, span)
            if got[0] == "retry":
                excluded.add(rep.name)
                last = got[1]
                continue
            return ("response", rep, got[1], got[2])
        # Every replica refused or nothing was routable.
        if span is not None:
            span.event("proxy_shed")
        if last is not None and last[1] in (429, 503):
            self._m_shed.inc()
            return ("shed", last[1], last[2], last[3])
        self._m_shed.inc()
        return ("shed", 503,
                json.dumps({"error": "no replica available",
                            "retryAfterSeconds": GATEWAY_RETRY_AFTER_S}
                           ).encode(),
                str(GATEWAY_RETRY_AFTER_S))

    # --- spillover: park all-shed requests instead of 429ing ----------------

    def _spill_wake(self) -> None:
        """Router-poll listener: capacity may have returned — wake every
        parked request so it retries now, not at its timer backstop."""
        with self._spill_lock:
            self._spill_cond.notify_all()

    def spill_or_shed(self, shed, retry, deadline_s: float, span=None):
        """An all-shed verdict enters the bounded spillover queue: the
        request parks at the gateway and re-routes when a replica frees
        (router-poll wakeup, 50ms timer backstop) instead of passing the
        429/503 through — a brief storm becomes client latency, never an
        error. Three ways out:

          - a retry wins a replica: return its ("response"/"inline", ...)
            verdict (outcome ``recovered``);
          - the deadline expires while parked: ("spill_timeout", shed) —
            the handler renders the in-band timeout terminal;
          - the queue is full, or the ``gateway.spill`` fault point is
            armed: the ORIGINAL shed verdict passes through untouched
            (bounded queue + chaos both degrade to the pre-spillover
            contract, they never deadlock a handler thread).

        ``retry`` re-runs this request's routing (single-hop or the
        disaggregated two-stage driver); ``shed`` is refreshed on every
        re-shed so a final passthrough carries the newest Retry-After."""
        try:
            faults.maybe_fail("gateway.spill")
        except faults.FaultInjected:
            self._m_spill.inc(outcome="fault")
            return shed
        with self._spill_lock:
            if self._spill_depth >= self.spill_capacity:
                self._m_spill.inc(outcome="overflow")
                return shed
            self._spill_depth += 1
        t0 = time.monotonic()
        deadline = t0 + max(0.0, deadline_s)
        if span is not None:
            span.event("spill_park")
        try:
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._m_spill.inc(outcome="timeout")
                    return ("spill_timeout", shed)
                with self._spill_lock:
                    self._spill_cond.wait(
                        timeout=min(SPILL_WAIT_TICK_S, remaining))
                if not self.router.ready_count():
                    # Nothing routable at all: retrying now would only
                    # stampede the poll path. The background poll promotes
                    # a recovered replica and wakes us.
                    continue
                got = retry()
                if got[0] != "shed":
                    self._m_spill.inc(outcome="recovered")
                    if span is not None:
                        span.event("spill_resume")
                    return got
                shed = got
        finally:
            self._m_spill_wait.observe(time.monotonic() - t0)
            with self._spill_lock:
                self._spill_depth -= 1
                self._spill_cond.notify_all()

    # --- disaggregated two-stage routing (KV handoff) ----------------------

    def handoff_and_proxy(self, req: dict, body: bytes,
                          prefix_id: str | None, stream: bool, span=None):
        """Two-stage routing for ``/v1/generate`` when the replica census
        declares roles: export the prompt's KV from a prefill replica
        (picked by queue depth), import it into a decode replica (picked by
        the same rendezvous prefix affinity as the mixed path), and hand
        the decode replica's live response back for relaying. Both hops
        carry this span's traceparent, so the prefill-cell and decode-cell
        engine spans land as children of ONE gateway span.

        Degradation contract (the ``kv.handoff`` robustness satellite):
        any stage failing — import 5xx, decode replica dead or shedding,
        no decode replica ready — falls back to single-cell local decode
        on a prefill-capable replica instead of surfacing a handoff 5xx;
        the client sees 200, or the usual 429/503 shed when genuinely
        nothing has capacity.

        Returns select_and_proxy's shapes plus
        ``("inline", status, payload, content_type)`` when the gateway can
        answer from the export header alone (first token already
        terminal, or a 400 passing through)."""
        t0 = time.monotonic()
        excluded: set[str] = set()   # hard: connect error / 429 / 503
        soft: set[str] = set()       # handoff-5xx: still fallback-eligible
        fwd_headers = (
            {obs_trace.TRACEPARENT_HEADER: obs_trace.format_traceparent(
                span.trace_id, span.span_id)}
            if span is not None else None)

        def fallback(stage: str):
            if span is not None:
                span.event("handoff_fallback", stage=stage)
            self._m_handoff_fallback.inc()
            return self.select_and_proxy("/v1/generate", body, prefix_id,
                                         span=span, pool="prefill",
                                         exclude=excluded)

        # --- stage 1: prefill export (queue-depth pick) --------------------
        export_req = dict(req)
        export_req.pop("stream", None)
        ebody = json.dumps(export_req).encode()
        export = None
        last: tuple | None = None
        repolled = False
        attempts = 0
        while attempts < max(1, len(self.router._pool_members("prefill"))):
            rep, policy = self.router.pick_prefill(exclude=excluded | soft)
            if rep is None:
                if not repolled:
                    repolled = True
                    self.router.poll_once()
                    continue
                break
            attempts += 1
            self._m_routing.inc(policy=policy)
            if span is not None:
                span.event("proxy_attempt", replica=rep.name, policy=policy,
                           stage="export")
            got = self._try_replica(rep, "/v1/kv/export", ebody, fwd_headers,
                                    span, stage="export")
            if got[0] == "retry":
                excluded.add(rep.name)
                last = got[1]
                continue
            _tag, conn, resp = got
            if resp.status != 200:
                payload = resp.read()
                ctype = resp.getheader("Content-Type") or "application/json"
                conn.close()
                rep.end()
                self._m_requests.inc(replica=rep.name,
                                     outcome=f"status_{resp.status}")
                if resp.status == 400:
                    # The client's problem — pass it through untouched.
                    return ("inline", 400, payload, ctype)
                self._m_handoff_failures.inc(stage="export")
                soft.add(rep.name)
                continue
            data = resp.read()
            conn.close()
            rep.end()
            self._m_requests.inc(replica=rep.name, outcome="ok")
            nl = data.find(b"\n")
            try:
                header = json.loads(data[:max(nl, 0)])
            except ValueError:
                self._m_handoff_failures.inc(stage="export")
                soft.add(rep.name)
                continue
            export = (rep.name, header, data[nl + 1:])
            break
        if export is None:
            if last is not None and last[1] in (429, 503):
                # Every prefill-capable replica shed: same passthrough
                # semantics as the single-hop path.
                if span is not None:
                    span.event("proxy_shed")
                self._m_shed.inc()
                return ("shed", last[1], last[2], last[3])
            return fallback("export")

        prefill_name, header, raw = export
        if header.get("done"):
            # The first token is already terminal (eos / stop / one-token
            # budget): no decode hop needed — answer from the header.
            first = int(header.get("token", -1))
            text = header.get("text") or ""
            secs = round(time.monotonic() - t0, 4)
            if stream:
                payload = (
                    json.dumps({"token": first, "text": text}) + "\n"
                    + json.dumps({"done": True, "tokens": [first],
                                  "text": text, "numTokens": 1,
                                  "seconds": secs}) + "\n").encode()
                return ("inline", 200, payload, "application/x-ndjson")
            payload = json.dumps({"tokens": [first], "text": text,
                                  "numTokens": 1, "seconds": secs}).encode()
            return ("inline", 200, payload, "application/json")

        # --- stage 2: decode import (prefix affinity pick) -----------------
        imp_header = dict(header)
        imp_header["stream"] = bool(stream)
        ibody = json.dumps(imp_header).encode() + b"\n" + raw
        repolled = False
        attempts = 0
        while attempts < max(1, len(self.router._pool_members("decode"))):
            rep, policy = self.router.pick_decode(prefix_id,
                                                  exclude=excluded | soft)
            if rep is None:
                if not repolled:
                    repolled = True
                    self.router.poll_once()
                    continue
                break
            attempts += 1
            self._m_routing.inc(policy=policy)
            if span is not None:
                span.event("proxy_attempt", replica=rep.name, policy=policy,
                           stage="import")
            got = self._try_replica(rep, "/v1/kv/import", ibody, fwd_headers,
                                    span, stage="import")
            if got[0] == "retry":
                excluded.add(rep.name)
                if got[1][1] is None:
                    # Connect failure = the decode replica died mid-
                    # handoff; a 429/503 is ordinary shedding, not a
                    # handoff fault.
                    self._m_handoff_failures.inc(stage="import")
                continue
            _tag, conn, resp = got
            if resp.status != 200:
                payload = resp.read()
                ctype = resp.getheader("Content-Type") or "application/json"
                conn.close()
                rep.end()
                self._m_requests.inc(replica=rep.name,
                                     outcome=f"status_{resp.status}")
                if resp.status == 400:
                    return ("inline", 400, payload, ctype)
                self._m_handoff_failures.inc(stage="import")
                soft.add(rep.name)
                continue
            # Handoff complete: account the move and relay the live
            # response (the import stream carries the first token line
            # the moment the decode cell emits it).
            n = int(header.get("length") or 0)
            pt = int(header.get("pageTokens") or 0)
            pages = (n // pt + 1) if pt else 1
            self._m_handoff_pages.inc(pages)
            self._m_handoff_bytes.inc(len(raw))
            self._m_handoff_seconds.observe(time.monotonic() - t0)
            if span is not None:
                span.event("kv_handoff", prefill=prefill_name,
                           decode=rep.name, pages=pages, bytes=len(raw))
            return ("response", rep, conn, resp)
        return fallback("import")

    def stats(self) -> dict:
        reg = self.registry
        return {
            "model": self.model_name,
            "kind": "gateway",
            "uptimeSeconds": round(time.time() - self.started_at, 1),
            "replicas": [r.snapshot() for r in self.router.replicas],
            "readyReplicas": self.router.ready_count(),
            "requests": int(sum(
                v for _l, v in reg.get(
                    "kukeon_gateway_requests_total").samples())),
            "retries": int(sum(
                v for _l, v in reg.get(
                    "kukeon_gateway_retries_total").samples())),
            "shed": int(reg.get("kukeon_gateway_shed_total").value()),
            "spill": {
                "depth": self._spill_depth,
                "capacity": self.spill_capacity,
                **{k: int(reg.get("kukeon_gateway_spill_total").value(
                    outcome=k))
                   for k in ("recovered", "timeout", "overflow", "fault")},
            },
            # The gateway admits while >=1 replica does; surfacing the same
            # ready/draining keys as a serving cell keeps pollers uniform.
            "ready": self.router.ready_count() > 0,
            "draining": False,
            "queueDepth": sum(r.queue_depth for r in self.router.replicas),
        }


def make_gateway_handler(gw: GatewayCell):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):
            sys.stderr.write("gateway: " + fmt % a + "\n")

        def _send(self, code: int, obj: dict,
                  headers: dict[str, str] | None = None):
            body = json.dumps(obj).encode()
            self._send_raw(code, body, "application/json", headers)

        def _send_raw(self, code: int, body: bytes, content_type: str,
                      headers: dict[str, str] | None = None):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path = urlsplit(self.path).path
            if path in ("/healthz", "/v1/health"):
                self._send(200, {"status": "ok", "model": gw.model_name,
                                 "kind": "gateway"})
            elif path == "/readyz":
                n = gw.router.ready_count()
                if n:
                    self._send(200, {"ready": True, "readyReplicas": n})
                else:
                    self._send(503, {"ready": False,
                                     "reason": "no replica ready"})
            elif path == "/v1/stats":
                self._send(200, gw.stats())
            elif path == "/metrics":
                self._send_raw(200, expo.render(gw.registry).encode(),
                               expo.CONTENT_TYPE)
            elif path == "/v1/trace":
                # Gateway-side proxy spans (attempts, retry hops, shed
                # outcomes) — the front-door half of the federated trace
                # timeline; same query surface as the serving cells.
                q = parse_qs(urlsplit(self.path).query)
                if "trace_id" in q:
                    self._send(200, {"spans":
                                     gw.tracer.for_trace(q["trace_id"][0])})
                    return
                if "request_id" in q:
                    try:
                        rid = int(q["request_id"][0])
                    except ValueError:
                        self._send(400, {"error":
                                         "request_id must be an integer"})
                        return
                    self._send(200, {"spans": gw.tracer.for_request(rid)})
                    return
                try:
                    n = int(q.get("n", ["50"])[0])
                except ValueError:
                    self._send(400, {"error": "n must be an integer"})
                    return
                self._send(200, {"spans": gw.tracer.recent(n)})
            else:
                self._send(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            path = urlsplit(self.path).path
            if path not in ("/v1/generate", "/v1/embed"):
                self._send(404, {"error": f"no route {self.path}; this "
                                          "gateway proxies /v1/generate "
                                          "and /v1/embed"})
                return
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            try:
                req = json.loads(body or b"{}")
                if not isinstance(req, dict):
                    raise ValueError("request body must be a JSON object")
            except ValueError as e:
                self._send(400, {"error": f"invalid JSON body: {e}"})
                return
            prefix_id = None
            stream = False
            if path == "/v1/generate":
                prefix_id = req.get("prefixId")
                if prefix_id is not None and not isinstance(prefix_id, str):
                    self._send(400, {"error": "prefixId must be a string"})
                    return
                stream = bool(req.get("stream"))

            # The proxy span: joins the client's trace when a traceparent
            # header came in, else roots a fresh one; every replica
            # attempt lands on it and the downstream hop inherits it.
            span = gw.begin_span(path, obs_trace.parse_traceparent(
                self.headers.get(obs_trace.TRACEPARENT_HEADER)))
            if path == "/v1/generate" and gw.router.disaggregated():
                # Role census says this fleet is disaggregated: drive the
                # two-stage prefill-export -> decode-import handoff.
                def route():
                    return gw.handoff_and_proxy(req, body, prefix_id,
                                                stream, span=span)
            else:
                def route():
                    return gw.select_and_proxy(path, body, prefix_id,
                                               span=span)
            got = route()
            if got[0] == "shed":
                # Spillover: every replica shed (or nothing was routable).
                # Park the request and retry until a replica frees or the
                # deadline runs out, bounded by the spill queue capacity.
                d = req.get("deadlineS")
                wait = (min(float(d), gw.spill_max_wait_s)
                        if isinstance(d, (int, float)) and d > 0
                        else gw.spill_max_wait_s)
                got = gw.spill_or_shed(got, route, wait, span=span)
            if got[0] == "spill_timeout":
                # The deadline expired while parked. Mirror the serving
                # cell's timeout contract: 504 + timedOut for a plain
                # request; an in-band terminal line for a stream (the
                # client asked for ndjson and nothing has been sent yet).
                msg = {"error": "deadline exceeded while queued at the "
                                "gateway (all replicas shedding)",
                       "timedOut": True, "numTokens": 0}
                gw.finish_span(span, "timeout")
                if stream:
                    self._send_raw(200, (json.dumps(msg) + "\n").encode(),
                                   "application/x-ndjson")
                else:
                    self._send(504, msg)
                return
            if got[0] == "inline":
                # The gateway answered from the export header (terminal
                # first token) or passes a 400 through.
                _tag, status, payload, ctype = got
                gw.finish_span(span, "ok" if status < 400 else "error",
                               status=status)
                self._send_raw(status, payload or b"{}", ctype)
                return
            if got[0] == "shed":
                _tag, status, payload, retry_after = got
                secs = float(retry_after or GATEWAY_RETRY_AFTER_S)
                gw.finish_span(span, "shed", status=status)
                self._send_raw(status, payload or b"{}", "application/json",
                               {"Retry-After": str(max(1, math.ceil(secs)))})
                return
            _tag, rep, conn, resp = got
            try:
                if stream and resp.status == 200:
                    self._relay_stream(rep, resp, span)
                else:
                    payload = resp.read()
                    headers = {}
                    ra = resp.getheader("Retry-After")
                    if ra:
                        headers["Retry-After"] = ra
                    gw._m_requests.inc(
                        replica=rep.name,
                        outcome="ok" if resp.status < 400 else
                        f"status_{resp.status}")
                    # The span is in the ring before the response's last
                    # byte is out: a client that reads /v1/trace the moment
                    # its answer arrives finds it (its outcome is the
                    # replica's; a client gone by now changes nothing the
                    # replica did).
                    gw.finish_span(
                        span, "ok" if resp.status < 400 else "error",
                        replica=rep.name, status=resp.status)
                    self._send_raw(
                        resp.status, payload,
                        resp.getheader("Content-Type") or "application/json",
                        headers)
            except OSError:
                # The replica's answer could not be read, or the client
                # went away mid-stream; nothing to tell it, but the span
                # still records the outcome (first finish wins — a stream
                # error already finished it in-band).
                gw.finish_span(span, "error", replica=rep.name,
                               detail="client disconnected")
            finally:
                conn.close()
                rep.end()

        def _relay_stream(self, rep, resp, span=None):
            """Byte-for-byte ndjson passthrough. The replica frames the
            stream by connection close (its handler speaks HTTP/1.0), so
            copying raw body chunks until EOF reproduces the payload
            exactly — UTF-8 split-codepoint holdback, in-band error lines
            and all. A replica dying mid-stream surfaces as an in-band
            terminal error line, never a retry (partial tokens are already
            on the client's wire) and never a second status line."""
            self.send_response(200)
            self.send_header("Content-Type",
                             resp.getheader("Content-Type")
                             or "application/x-ndjson")
            self.end_headers()
            trailing_newline = True
            try:
                while True:
                    # read1, not read: read(n) blocks for n bytes or EOF,
                    # which would buffer the whole close-framed stream and
                    # destroy token-streaming latency; read1 relays each
                    # token line the moment the replica flushes it.
                    chunk = resp.read1(STREAM_CHUNK)
                    if not chunk:
                        break
                    trailing_newline = chunk.endswith(b"\n")
                    self.wfile.write(chunk)
                    self.wfile.flush()
                gw._m_requests.inc(replica=rep.name, outcome="ok")
                gw.finish_span(span, "ok", replica=rep.name, stream=True)
            except Exception as e:  # noqa: BLE001 — headers are out; stay in-band
                gw._m_requests.inc(replica=rep.name, outcome="stream_error")
                gw.finish_span(span, "error", replica=rep.name, stream=True,
                               detail=f"{type(e).__name__}: {e}")
                gw.router.mark_unready(rep)
                try:
                    line = json.dumps({"error": "replica failed mid-stream: "
                                                f"{type(e).__name__}: {e}"})
                    if not trailing_newline:
                        # Keep the client's line parser intact: never glue
                        # the terminal error onto a half-written record.
                        self.wfile.write(b"\n")
                    self.wfile.write((line + "\n").encode())
                    self.wfile.flush()
                except OSError:
                    pass

    return Handler


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kukeon-gateway")
    ap.add_argument("--model", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--replica", action="append", required=True,
                    help="replica base URL (repeat per replica)")
    ap.add_argument("--poll-interval-s", type=float, default=0.5)
    ap.add_argument("--request-timeout-s", type=float, default=600.0)
    ap.add_argument("--spill-capacity", type=int, default=SPILL_CAPACITY,
                    help="max all-shed requests parked in the spillover "
                         "queue (past it the shed passes through)")
    ap.add_argument("--spill-max-wait-s", type=float,
                    default=SPILL_MAX_WAIT_S,
                    help="longest a spilled request without its own "
                         "deadlineS waits before the timeout terminal")
    args = ap.parse_args(argv)

    gw = GatewayCell(args.model, args.replica,
                     poll_interval_s=args.poll_interval_s,
                     request_timeout_s=args.request_timeout_s,
                     spill_capacity=args.spill_capacity,
                     spill_max_wait_s=args.spill_max_wait_s)
    gw.start()
    server = ThreadingHTTPServer((args.host, args.port),
                                 make_gateway_handler(gw))

    import signal as _signal
    import threading as _threading

    # The gateway is stateless: SIGTERM just stops the listener (off-thread
    # — shutdown() blocks until serve_forever returns, and the signal
    # handler runs on the serving thread). In-flight proxied requests ride
    # their own handler threads to completion.
    _signal.signal(_signal.SIGTERM, lambda *_a: _threading.Thread(
        target=server.shutdown, daemon=True).start())

    print(f"gateway: {args.model} routing {len(args.replica)} replicas "
          f"on {args.host}:{args.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
