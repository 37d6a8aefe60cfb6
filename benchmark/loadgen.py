"""Open-loop load from one process that never imports JAX.

A dispatcher thread sends each request at the moment it is due, whether or not
earlier ones have finished; one short-lived thread per request in flight reads
the cell's ndjson stream and stamps every token line. Times are
``time.monotonic()`` of this process, and a request's clock starts when it was
DUE, so a stalled server is charged for the wait it imposes on later requests.
"""

from __future__ import annotations

import heapq
import http.client
import json
import threading
import time


def post_generate(port: int, request: dict, timeout_s: float) -> dict:
    """One streamed /v1/generate; returns status, tokens and token times."""
    body = json.dumps({
        "promptTokens": request["prompt"], "stream": True,
        "maxNewTokens": request["max_new_tokens"], "temperature": 0,
        **({"prefixId": request["prefix_id"]} if request["prefix_id"] else {}),
    })
    out = {"status": 0, "tokens": [], "token_times": [], "error": None}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read(300).decode("utf-8", "replace")
            return out
        for line in resp:
            rec = json.loads(line)
            if "token" in rec:
                out["token_times"].append(time.monotonic())
            elif rec.get("done"):
                out["tokens"] = rec["tokens"]
            elif "error" in rec:
                out["error"] = rec["error"]
    except (OSError, ValueError, http.client.HTTPException) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return out


def call(port: int, method: str, path: str, obj: dict | None = None,
         timeout_s: float = 30.0) -> tuple[int, bytes]:
    """One small request to the cell (/metrics, /v1/profile, ...)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        body = None if obj is None else json.dumps(obj)
        conn.request(method, path, body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class OpenLoop:
    """Runs a generator's requests against the cell.

    ``t0`` is the monotonic time of the window's start; a request's ``due`` is
    an offset from it (negative inside a ramp). Offering stops at
    ``t0 + seconds``; what is in flight then has ``drain_s`` to finish.
    ``at`` holds (offset, callable) pairs run on the dispatcher's clock, which
    is how a traced run asks for its capture. ``missed``, after ``run()``,
    lists the (offset, callable) pairs that were due inside the window and
    that the dispatcher never came to (a stalled host): the caller decides
    which of them may still run, late."""

    def __init__(self, port: int, generator, t0: float, seconds: float,
                 drain_s: float, at: list | None = None):
        self.port, self.gen = port, generator
        self.t0, self.end = t0, t0 + seconds
        self.deadline = self.end + drain_s
        self._heap: list = []
        self._n = 0
        self._cv = threading.Condition()
        self._threads: list[threading.Thread] = []
        self.records: list[dict] = []
        self.missed: list = []
        self._inflight: dict = {}
        for r in generator.arrivals():
            self._push(r)
        for offset, fn in at or []:
            self._push({"due": offset, "call": fn})

    def _push(self, item: dict) -> None:
        with self._cv:
            self._n += 1
            heapq.heappush(self._heap, (self.t0 + item["due"], self._n, item))
            self._cv.notify()

    def _record(self, request: dict, due: float, sent: float, done,
                ok: bool, res: dict) -> dict:
        return {"id": request["id"], "due": due, "sent": sent, "done": done,
                "ok": ok, "status": res["status"], "error": res["error"],
                "prompt": request["prompt"],
                "prefix_id": request["prefix_id"],
                "prompt_len": len(request["prompt"]),
                "new_tokens": request["new_tokens"],
                "asked": request["max_new_tokens"], "tokens": res["tokens"],
                "token_times": res["token_times"],
                "in_window": self.t0 <= due < self.end}

    def _serve(self, request: dict, due: float) -> None:
        sent = time.monotonic()
        res = post_generate(self.port, request,
                            max(1.0, self.deadline - sent))
        done = time.monotonic()
        ok = (res["status"] == 200 and res["error"] is None
              and len(res["tokens"]) == request["max_new_tokens"]
              and len(res["token_times"]) == len(res["tokens"])
              and done <= self.deadline)
        rec = self._record(request, due, sent, done, ok, res)
        follow = self.gen.on_complete(request, res["tokens"],
                                      done - self.t0) if ok else []
        with self._cv:
            self.records.append(rec)
            self._inflight.pop(request["id"], None)
        for r in follow:
            self._push(r)

    def run(self) -> list[dict]:
        """Blocks until the window has closed and what was in flight has
        finished or the drain limit has passed. Returns every record."""
        while True:
            with self._cv:
                now = time.monotonic()
                if now >= self.end:
                    break
                wait = (self._heap[0][0] - now) if self._heap \
                    else (self.end - now)
                if wait > 0:
                    self._cv.wait(min(wait, self.end - now))
                    continue
                due, _n, item = heapq.heappop(self._heap)
            if due >= self.end:
                continue
            if "call" in item:
                threading.Thread(target=item["call"], daemon=True).start()
                continue
            t = threading.Thread(target=self._serve, args=(item, due),
                                 daemon=True)
            with self._cv:
                self._inflight[item["id"]] = (item, due)
            t.start()
            self._threads.append(t)
        with self._cv:
            self.missed = [(due - self.t0, item["call"])
                           for due, _n, item in sorted(self._heap)
                           if "call" in item and due < self.end]
        for t in self._threads:
            t.join(max(0.0, self.deadline + 2.0 - time.monotonic()))
        with self._cv:
            lost = {"status": 0, "error": "not drained", "tokens": [],
                    "token_times": []}
            stuck = [self._record(req, due, due, None, False, lost)
                     for req, due in self._inflight.values()]
            return list(self.records) + stuck
