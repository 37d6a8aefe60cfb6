"""Serving resilience: admission control, deadlines, lifecycle endpoints,
TPU watchdog, and engine recovery — each failure *injected* via the fault
harness (kukeon_tpu.faults), never timed.

Engine-level tests drive step() manually for determinism; the HTTP class
runs one cell through the full lifecycle story in definition order."""

from __future__ import annotations

import http.client
import json
import os
import threading
import time
from http.server import ThreadingHTTPServer

import jax
import numpy as np
import pytest

from kukeon_tpu import faults
from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import (
    DeadlineExceeded,
    RejectedError,
    SamplingParams,
    ServingEngine,
)


def _tiny_engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 1)
    return ServingEngine(cfg, params, mesh, max_seq_len=96,
                         decode_chunk=4, **kw)


PROMPT = np.arange(1, 9, dtype=np.int32)


# --- admission control ------------------------------------------------------


def test_queue_full_sheds_with_rejected_error():
    eng = _tiny_engine(max_pending=2)
    a = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    b = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    assert eng.queue_depth == 2
    with pytest.raises(RejectedError) as ei:
        eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    assert ei.value.retry_after_s > 0
    assert eng.shed_stats["rejected"] == 1
    # Shedding is not sticky: drain the queue and submits are admitted again.
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()
    assert eng.queue_depth == 0
    c = eng.generate(PROMPT, SamplingParams(max_new_tokens=2))
    assert len(c) == 2
    assert eng.shed_stats["rejected"] == 1


def test_slotted_requests_do_not_count_against_max_pending():
    """max_pending bounds the QUEUE, not concurrency: once a request is
    slotted it stops counting, so num_slots + max_pending requests coexist."""
    eng = _tiny_engine(num_slots=2, max_pending=1)
    a = eng.submit(PROMPT, SamplingParams(max_new_tokens=32))
    eng.step()                      # a takes a slot; queue is empty again
    assert eng.queue_depth == 0
    b = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    assert eng.queue_depth == 1
    a.cancel()
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()


# --- deadlines --------------------------------------------------------------


def test_queued_request_past_deadline_times_out_in_band():
    eng = _tiny_engine()
    hog = eng.submit(PROMPT, SamplingParams(max_new_tokens=64))
    eng.step()                      # hog occupies THE slot
    events: list[tuple[int, bool]] = []
    victim = eng.submit(PROMPT, SamplingParams(max_new_tokens=4),
                        emit=lambda t, d: events.append((t, d)),
                        deadline_s=0.01)
    time.sleep(0.03)
    eng.step()
    assert victim.done.is_set()
    assert victim.timed_out
    assert isinstance(victim.error, DeadlineExceeded)
    assert events == [(-1, True)]   # in-band terminal event, no token
    assert eng.shed_stats["timed_out"] == 1
    hog.cancel()
    while not hog.done.is_set():
        eng.step()


def test_active_request_deadline_frees_slot_and_keeps_partial_output():
    eng = _tiny_engine()
    victim = eng.submit(PROMPT, SamplingParams(max_new_tokens=64),
                        deadline_s=0.2)
    waiter = eng.submit(PROMPT, SamplingParams(max_new_tokens=3))
    deadline = time.monotonic() + 60
    while not (victim.done.is_set() and waiter.done.is_set()):
        assert time.monotonic() < deadline, "deadline expiry left a hang"
        eng.step()
    assert victim.timed_out
    assert len(victim.generated) < 64       # stopped at the deadline...
    assert waiter.generated and len(waiter.generated) == 3  # ...slot reused
    assert len(eng._free_slots()) == eng.num_slots
    assert not eng._requests
    assert eng.shed_stats["timed_out"] == 1


def test_generate_surfaces_deadline_error():
    eng = _tiny_engine()
    req = eng.submit(PROMPT, SamplingParams(max_new_tokens=500),
                     deadline_s=0.05)
    while not req.done.is_set():
        eng.step()
    assert req.timed_out and isinstance(req.error, DeadlineExceeded)


def test_submit_rejects_nonpositive_deadline():
    eng = _tiny_engine()
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(PROMPT, SamplingParams(max_new_tokens=1), deadline_s=0.0)


# --- fault-injected engine failures ----------------------------------------


@pytest.mark.faults
def test_engine_thread_recovers_from_injected_decode_fault():
    """One poisoned decode chunk fails the in-flight request but the engine
    loop rebuilds state and keeps serving (the _fail_all + re-init path,
    exercised by injection instead of hoping for a real XLA error)."""
    eng = _tiny_engine()
    os.environ[faults.ENV] = "engine.decode:1:1"
    eng.start()
    try:
        r1 = eng.submit(PROMPT, SamplingParams(max_new_tokens=4))
        assert r1.done.wait(60)
        assert isinstance(r1.error, faults.FaultInjected)
        assert faults.fired("engine.decode") == 1
        # The injected fault is exhausted (count=1): service continues.
        r2 = eng.submit(PROMPT, SamplingParams(max_new_tokens=4))
        assert r2.done.wait(60)
        assert r2.error is None
        assert len(r2.generated) == 4
        assert isinstance(eng.error, faults.FaultInjected)
    finally:
        eng.stop()


@pytest.mark.faults
def test_manual_step_prefill_fault_fails_only_that_request():
    eng = _tiny_engine()
    os.environ[faults.ENV] = "engine.prefill:1:1"
    r = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    with pytest.raises(faults.FaultInjected):
        eng.step()
    # The popped-but-never-slotted request was failed, not leaked.
    assert r.done.is_set()
    assert isinstance(r.error, faults.FaultInjected)
    assert eng.queue_depth == 0
    # Engine state is untouched (the fault fired before any dispatch).
    ok = eng.generate(PROMPT, SamplingParams(max_new_tokens=2))
    assert len(ok) == 2


# --- TPU watchdog -----------------------------------------------------------


class _StalledEngine:
    """Engine stand-in with a controllable progress heartbeat."""

    def __init__(self, busy=True):
        self.busy = busy
        # Same guarded heartbeat shape as the real engine: the watchdog
        # re-arms the heartbeat under this lock on a healthy probe.
        self._lock = threading.Lock()
        self.last_progress = time.monotonic()

    def stalled_s(self) -> float:
        if not self.busy:
            return 0.0
        return time.monotonic() - self.last_progress


def _watchdog(eng, probe, budget=0.05, **kw):
    from kukeon_tpu.runtime.serving_cell import EngineWatchdog

    return EngineWatchdog(eng, stall_budget_s=budget, probe=probe,
                          interval_s=0.01, **kw)


def test_watchdog_trips_on_wedged_probe():
    eng = _StalledEngine()
    eng.last_progress -= 10          # already stalled way past the budget
    hits: list[str] = []
    wd = _watchdog(eng, probe=lambda timeout_s: ("wedged", "probe hung"),
                   on_wedged=hits.append)
    wd.start()
    wd.join(timeout=5)
    assert not wd.is_alive()         # trip terminates the watchdog thread
    assert wd.tripped
    assert hits == ["probe hung"]
    assert wd.last_verdict == ("wedged", "probe hung")


def test_watchdog_rearms_on_healthy_probe():
    """A slow-but-alive runtime (long compile, giant prefill) must NOT get
    the cell killed: an ok probe re-arms the budget instead of tripping."""
    eng = _StalledEngine()
    eng.last_progress -= 10
    wd = _watchdog(eng, probe=lambda timeout_s: ("ok", "backend=cpu"))
    wd.start()
    try:
        deadline = time.monotonic() + 5
        while wd.probes == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert wd.probes >= 1
        assert not wd.tripped
        # The probe bumped the heartbeat: the stall clock restarted.
        assert eng.stalled_s() < 5
    finally:
        wd.stop()
        wd.join(timeout=5)


def test_watchdog_never_probes_an_idle_engine():
    eng = _StalledEngine(busy=False)
    wd = _watchdog(eng, probe=lambda timeout_s: ("wedged", "must not run"))
    wd.start()
    try:
        time.sleep(0.1)
        assert wd.probes == 0
        assert not wd.tripped
    finally:
        wd.stop()
        wd.join(timeout=5)


def test_stall_clock_runs_while_a_request_is_inside_prefill_dispatch():
    """Between leaving the queue and taking its slot a request sits in
    prefill dispatch — where a first-use compile (or a hung device call)
    stalls the driver while queue depth and slot occupancy both read idle.
    The watchdog's stall clock must see that request."""
    eng = _tiny_engine()
    seen: list[float] = []
    real = eng._prefill

    def slow_prefill(*a, **kw):
        time.sleep(0.05)
        seen.append(eng.stalled_s())
        return real(*a, **kw)

    eng._prefill = slow_prefill
    assert eng.stalled_s() == 0.0            # idle: never stalled
    r = eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
    while not r.done.is_set():
        eng.step()
    assert seen and seen[0] >= 0.05
    assert eng.stalled_s() == 0.0


@pytest.mark.faults
@pytest.mark.parametrize("probe_name", ["probe_tpu_runtime",
                                        "probe_tpu_in_process"])
def test_probe_reports_wedged_under_fault_injection(probe_name):
    """The devices.probe_wedged fault seam, on both probes (the subprocess
    form `kuke doctor` uses and the in-process form the cell's watchdog
    uses): the wedged verdict (and so the whole watchdog->exit->restart
    chain) is reachable without a chip."""
    from kukeon_tpu.runtime import devices

    os.environ[faults.ENV] = "devices.probe_wedged:1"
    status, detail = getattr(devices, probe_name)(timeout_s=5)
    assert status == "wedged"
    assert "fault-injected" in detail


def test_in_process_probe_answers_from_this_process(monkeypatch):
    """The cell holds the chip, so the watchdog's question goes to THIS
    process's own runtime client — a tiny transfer from a probe-owned
    thread — and never to a second process (which could not open an
    attached chip its parent holds)."""
    import subprocess

    from kukeon_tpu.runtime.devices import probe_tpu_in_process

    def no_child(*a, **kw):
        raise AssertionError("the in-process probe started a process")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)
    status, detail = probe_tpu_in_process(timeout_s=30)
    assert status == "ok", detail
    assert "backend=cpu" in detail


@pytest.mark.parametrize("behaviour,want", [("hangs", "wedged"),
                                            ("raises", "unavailable")])
def test_in_process_probe_verdicts(monkeypatch, behaviour, want):
    """A transfer that never returns is the wedge (bounded wait, verdict
    'wedged'); one that raises is 'unavailable' — the engine loop's own
    error path owns that failure, the watchdog re-arms."""
    from kukeon_tpu.runtime.devices import probe_tpu_in_process

    release = threading.Event()

    def device_put(x, *a, **kw):
        if behaviour == "hangs":
            release.wait(timeout=30)
            return x
        raise RuntimeError("device went away")

    monkeypatch.setattr(jax, "device_put", device_put)
    try:
        t0 = time.monotonic()
        status, detail = probe_tpu_in_process(timeout_s=0.2)
        assert status == want, detail
        assert time.monotonic() - t0 < 10        # the wait is bounded
        assert ("did not finish" in detail) == (want == "wedged")
    finally:
        release.set()


@pytest.mark.faults
def test_watchdog_default_probe_uses_devices_seam():
    """EngineWatchdog with no probe override consults the real
    probe_tpu_in_process — wired shut by the fault seam."""
    eng = _StalledEngine()
    eng.last_progress -= 10
    hits: list[str] = []
    os.environ[faults.ENV] = "devices.probe_wedged:1"
    wd = _watchdog(eng, probe=None, on_wedged=hits.append)
    wd.start()
    wd.join(timeout=10)
    assert wd.tripped
    assert hits and "fault-injected" in hits[0]


@pytest.mark.faults
def test_wedged_cell_exits_nonzero_end_to_end(tmp_path):
    """Full chain in a real cell process: KUKEON_FAULTS makes the runtime
    probe report wedged; a request stalls the engine past the (tiny)
    watchdog budget (its first step sits in jit compilation — a genuine
    multi-second device-side stall); the watchdog trips and the process
    exits WEDGED_EXIT_CODE — the exit the runner's restart policy turns
    into a restart on the same chip grant
    (test_runner_restart_edges.test_crash_looping_model_cell_keeps_its_chip_grant)."""
    import socket as _socket
    import subprocess
    import sys
    import urllib.request

    from kukeon_tpu.runtime.serving_cell import WEDGED_EXIT_CODE

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "KUKEON_WATCHDOG_S": "0.3",
        "KUKEON_WATCHDOG_PROBE_TIMEOUT_S": "5",
        "KUKEON_FAULTS": "devices.probe_wedged:1",
        # A fresh compilation cache: the stall under test IS the compile.
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache"),
    })
    log = open(tmp_path / "cell.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kukeon_tpu.runtime.serving_cell",
         "--model", "tiny", "--port", str(port), "--no-warmup",
         "--max-seq-len", "64", "--num-slots", "2"],
        env=env, stdout=log, stderr=log,
    )
    try:
        deadline = time.monotonic() + 240
        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=2).read()
                break
            except Exception:  # noqa: BLE001 — still booting
                if proc.poll() is not None:
                    raise AssertionError(
                        f"cell died before serving: rc={proc.returncode}, "
                        f"log:\n{(tmp_path / 'cell.log').read_bytes().decode(errors='replace')[-2000:]}"
                    ) from None
                assert time.monotonic() < deadline, "cell never came up"
                time.sleep(0.2)

        def fire():
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/generate",
                    data=json.dumps({"prompt": "hi",
                                     "maxNewTokens": 32}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=120).read()
            except Exception:  # noqa: BLE001 — the cell dies under us; expected
                pass

        threading.Thread(target=fire, daemon=True).start()
        rc = proc.wait(timeout=120)
        assert rc == WEDGED_EXIT_CODE
        tail = (tmp_path / "cell.log").read_bytes().decode(errors="replace")
        assert "watchdog tripped" in tail
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()


def test_compile_stall_past_watchdog_budget_is_not_killed(tmp_path):
    """A real cell process with a tiny stall budget and NO fault armed: its
    first request sits in jit compilation for seconds — a stall well past
    the budget on a device this process holds. The watchdog probes
    in-process, gets 'ok', re-arms, and the request is answered: slow is
    not wedged."""
    import socket as _socket
    import subprocess
    import sys
    import urllib.request

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "KUKEON_WATCHDOG_S": "0.3",
        # A fresh compilation cache: the stall under test IS the compile.
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache"),
    })
    with open(tmp_path / "cell.log", "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "kukeon_tpu.runtime.serving_cell",
             "--model", "tiny", "--port", str(port), "--no-warmup",
             "--max-seq-len", "64", "--num-slots", "2"],
            env=env, stdout=log, stderr=log,
        )
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 240
        while True:
            try:
                urllib.request.urlopen(f"{base}/healthz", timeout=2).read()
                break
            except Exception:  # noqa: BLE001 — still booting
                assert proc.poll() is None, "cell died before serving"
                assert time.monotonic() < deadline, "cell never came up"
                time.sleep(0.2)
        out = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": "hi", "maxNewTokens": 8}).encode(),
            headers={"Content-Type": "application/json"}),
            timeout=240).read())
        assert out["numTokens"] == 8
        assert proc.poll() is None, "the watchdog killed a compiling cell"
        text = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=10).read().decode()
        ok = [ln for ln in text.splitlines() if ln.startswith(
            'kukeon_watchdog_probes_total{verdict="ok"}')]
        assert ok and float(ok[0].split()[-1]) >= 1, \
            "the compile stall never reached the in-process probe"
        assert "kukeon_watchdog_trips_total 0" in text
    finally:
        proc.kill()
        proc.wait(timeout=30)


# --- HTTP lifecycle ---------------------------------------------------------


@pytest.fixture(scope="module")
def http_cell():
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    cell = ServingCell("tiny", num_slots=1, max_seq_len=96, checkpoint=None,
                       dtype=None, max_pending=2)
    cell.engine.start()
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield cell, server.server_address[1]
    server.shutdown()
    server.server_close()
    cell.engine.stop()


def _req(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read()
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, (json.loads(raw) if raw else {}), headers


class TestHTTPLifecycle:
    """One cell through its whole life: unready -> ready -> shedding ->
    timing out -> draining. Ordered; later tests depend on earlier state."""

    def test_unready_until_marked(self, http_cell):
        cell, port = http_cell
        status, body, _ = _req(port, "GET", "/healthz")
        assert status == 200                       # alive even while warming
        status, body, _ = _req(port, "GET", "/readyz")
        assert status == 503 and body["ready"] is False
        assert "warming" in body["reason"]
        # Admission is lifecycle-gated: 503 + Retry-After, not a hang.
        status, body, headers = _req(port, "POST", "/v1/generate",
                                     {"prompt": "hi", "maxNewTokens": 2})
        assert status == 503
        assert int(headers["Retry-After"]) >= 1

    def test_ready_serves(self, http_cell):
        cell, port = http_cell
        cell.mark_ready()
        status, body, _ = _req(port, "GET", "/readyz")
        assert status == 200 and body["ready"] is True
        status, body, _ = _req(port, "POST", "/v1/generate",
                               {"prompt": "hi", "maxNewTokens": 3,
                                "deadlineS": 60})
        assert status == 200
        assert body["numTokens"] == 3

    def test_queue_full_returns_429_with_retry_after(self, http_cell):
        cell, port = http_cell
        eng = cell.engine
        eng.stop()                                 # freeze the driver
        try:
            held = [eng.submit(PROMPT, SamplingParams(max_new_tokens=2))
                    for _ in range(2)]             # fill max_pending=2
            status, body, headers = _req(port, "POST", "/v1/generate",
                                         {"prompt": "hi", "maxNewTokens": 2})
            assert status == 429
            assert "Retry-After" in headers
            assert "queue full" in body["error"]
            status, stats, _ = _req(port, "GET", "/v1/stats")
            assert stats["rejected"] >= 1
            assert stats["queueDepth"] == 2
            assert stats["maxPending"] == 2
        finally:
            eng.start()                            # thaw; held reqs drain
        for r in held:
            assert r.done.wait(60)

    def test_deadline_timeout_is_in_band(self, http_cell):
        cell, port = http_cell
        hog = cell.engine.submit(PROMPT, SamplingParams(max_new_tokens=80))
        try:
            # Non-streaming: the timeout surfaces as 504 Gateway Timeout.
            status, body, _ = _req(port, "POST", "/v1/generate",
                                   {"prompt": "hi", "maxNewTokens": 4,
                                    "deadlineS": 0.01})
            assert status == 504
            assert body["timedOut"] is True
            # Streaming: headers are long gone when a mid-stream deadline
            # hits, so the timeout is an in-band terminal ndjson record.
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            conn.request("POST", "/v1/generate", body=json.dumps(
                {"prompt": "hi", "maxNewTokens": 4, "deadlineS": 0.01,
                 "stream": True}), headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            lines = [json.loads(x) for x in resp.read().decode().splitlines()]
            conn.close()
            assert lines[-1].get("timedOut") is True
            assert "deadline" in lines[-1]["error"]
            status, stats, _ = _req(port, "GET", "/v1/stats")
            assert stats["timedOut"] >= 2
        finally:
            hog.cancel()

    def test_drain_finishes_inflight_then_unready(self, http_cell):
        cell, port = http_cell
        inflight = cell.engine.submit(PROMPT,
                                      SamplingParams(max_new_tokens=24))
        status, body, _ = _req(port, "POST", "/drain")
        assert status == 200 and body["draining"] is True
        status, body, _ = _req(port, "GET", "/readyz")
        assert status == 503 and body["reason"] == "draining"
        # New work is refused while draining...
        status, body, headers = _req(port, "POST", "/v1/generate",
                                     {"prompt": "hi", "maxNewTokens": 2})
        assert status == 503 and "Retry-After" in headers
        # ...but the in-flight request FINISHES (never killed mid-decode).
        assert cell.drained.wait(30)
        assert inflight.done.is_set()
        assert len(inflight.generated) == 24
        assert not inflight.cancelled and inflight.error is None
        assert not cell.engine._running            # engine shut down
        # Drain is idempotent.
        assert cell.begin_drain() is False
