"""Device-level telemetry: HBM memory gauges, compile tracking, and the
on-demand profiler spool.

The request-level layer (registry/trace) answers "how slow"; this module
answers "why": is a bad p95 a recompile (``kukeon_compiles_total`` moving in
steady state), HBM pressure (``kukeon_hbm_bytes_in_use`` near the limit), or
a queue problem (neither)? Everything here imports jax lazily — the obs
package stays importable (and testable) without an accelerator runtime.

Three pieces:

- :func:`device_memory_collector` — a scrape-time collector over
  ``jax.Device.memory_stats()`` producing ``kukeon_hbm_bytes_in_use`` /
  ``_limit`` / ``_peak{device=}``. Backends without memory stats (CPU)
  declare the families with no samples, so dashboards and the golden parser
  see a stable schema everywhere.
- :class:`CompileTracker` — wraps the engine's jitted programs and detects
  tracing-cache growth around each dispatch, so every compile is counted
  (``kukeon_compiles_total{program=}``) and timed
  (``kukeon_compile_seconds{program=}``). The engine's "occupancy changes
  never recompile" docstring promise becomes a measurable invariant: after
  warmup the decode counter must stay flat, and a tier-1 test asserts it.
- :class:`ProfileSpool` — single-flight ``jax.profiler.trace`` captures into
  a bounded keep-last-K spool dir (``KUKEON_PROFILE_DIR``), driving the
  cells' ``POST /v1/profile`` endpoint.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from collections import deque

from kukeon_tpu import sanitize

# memory_stats() key -> exposed family. Every backend that reports memory
# uses these PJRT names (TPU, GPU); absent keys are simply skipped.
_HBM_FAMILIES = (
    ("bytes_in_use", "kukeon_hbm_bytes_in_use",
     "Device memory currently allocated, per device."),
    ("bytes_limit", "kukeon_hbm_bytes_limit",
     "Device memory capacity visible to the runtime, per device."),
    ("peak_bytes_in_use", "kukeon_hbm_bytes_peak",
     "High-water-mark device memory allocation, per device."),
)


def device_memory_collector():
    """Scrape-time HBM families from ``jax.Device.memory_stats()``.

    One sample per device per family; a device (or backend) without memory
    stats contributes no samples but the families are still declared — the
    scrape schema must not depend on which backend happens to be up.
    """
    import jax

    stats = []
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — a dead device must not kill the scrape
            ms = None
        if ms:
            stats.append((str(d.id), ms))
    for key, name, help in _HBM_FAMILIES:
        yield (name, "gauge", help,
               [({"device": dev}, float(ms[key]))
                for dev, ms in stats if key in ms])


def _cache_size(fn) -> int | None:
    """The jit tracing-cache entry count, or None when the runtime doesn't
    expose it (compile detection then degrades to 'unknown', never wrong)."""
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001 — private API; absence must not break dispatch
        return None


class _TrackedJit:
    """A jitted callable whose dispatches are watched for cache growth.

    Attribute access (``.lower``, ``.compile``) forwards to the underlying
    jit function so AOT precompilation paths keep working unchanged.

    ``timer`` (an ``obs.profile._ProgramTimer``) additionally marks every
    dispatch for deferred roofline timing: the mark is settled later
    inside the engine's counted ``_fetch`` seam, so timing adds zero
    blocking work here — dispatch stays async.
    """

    def __init__(self, fn, program: str, counter, seconds, timer=None):
        self._fn = fn
        self._program = program
        self._m_compiles = counter
        self._m_seconds = seconds
        self._timer = timer

    def __call__(self, *args, **kwargs):
        before = _cache_size(self._fn)
        t0 = time.monotonic()
        out = self._fn(*args, **kwargs)
        if before is not None:
            after = _cache_size(self._fn)
            if after is not None and after > before:
                self._m_compiles.inc(after - before, program=self._program)
                self._m_seconds.observe(time.monotonic() - t0,
                                        program=self._program)
        if self._timer is not None:
            self._timer.dispatched(t0, out)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class CompileTracker:
    """Registers the compile families and wraps jitted programs.

    A dispatch that grows the jit tracing cache was a (re)trace+compile:
    count it by program and record its wall time. Warmup compiles land here
    too (they are real compiles); the invariant under test is that the
    counters go FLAT afterwards — an unexpected steady-state retrace is the
    exact failure this makes visible.
    """

    def __init__(self, registry):
        self._m_compiles = registry.counter(
            "kukeon_compiles_total",
            "jit compiles observed at dispatch, by engine program "
            "(prefill|insert|decode). Flat in steady state.",
            labels=("program",))
        self._m_seconds = registry.histogram(
            "kukeon_compile_seconds",
            "Wall time of dispatches that compiled, by program.",
            labels=("program",))

    def wrap(self, fn, program: str, timer=None) -> _TrackedJit:
        """Wrap one jitted program. ``timer`` registers the program with
        the roofline seam (obs/profile.ProgramTimers.track) — kukelint
        KUKE015 requires every engine program to pass one."""
        return _TrackedJit(fn, program, self._m_compiles, self._m_seconds,
                           timer=timer)

    def count(self, program: str) -> int:
        return int(self._m_compiles.value(program=program))


class ProfileBusy(RuntimeError):
    """A capture is already running (single-flight; HTTP maps this to 409)."""


PROFILE_DIR_ENV = "KUKEON_PROFILE_DIR"
PROFILE_KEEP_ENV = "KUKEON_PROFILE_KEEP"
MAX_CAPTURE_MS = 600_000


class ProfileSpool:
    """Single-flight on-demand ``jax.profiler.trace`` captures.

    ``start(duration_ms)`` kicks a background thread that traces the live
    process for the requested window and writes the capture under the spool
    dir; only the newest K completed captures are kept (bounded disk, K from
    ``KUKEON_PROFILE_KEEP``). One capture at a time: profiling is itself a
    perturbation, and two overlapping jax traces would corrupt each other —
    a second start raises :class:`ProfileBusy`. Backends without a usable
    profiler produce a clear error record instead of a wedged endpoint.
    """

    def __init__(self, base_dir: str | None = None, keep: int | None = None,
                 registry=None):
        self.base_dir = (base_dir or os.environ.get(PROFILE_DIR_ENV)
                         or os.path.join(tempfile.gettempdir(),
                                         "kukeon-profiles"))
        self.keep = max(1, keep if keep is not None
                        else int(os.environ.get(PROFILE_KEEP_ENV, "4") or 4))
        self._lock = sanitize.lock("ProfileSpool._lock")
        self._active: dict | None = None   # guarded-by: _lock
        # Failed captures leave nothing on disk; keep their records so
        # GET /v1/profile can answer "why did my capture vanish".
        self._failed: deque[dict] = deque(maxlen=8)
        self._m_captures = None
        if registry is not None:
            self._m_captures = registry.counter(
                "kukeon_profile_captures_total",
                "On-demand profiler captures by outcome.",
                labels=("outcome",))

    def start(self, duration_ms: float, python_tracer: bool = False) -> dict:
        """Begin a capture; returns its record immediately (the trace runs
        in the background for ``duration_ms``). Raises ProfileBusy while a
        capture is in flight and ValueError on a bad duration.

        The host side of a capture is the engine loop's own spans
        (obs/spans.py). ``python_tracer`` adds the Python frames, which slow
        the host they time: hundreds of thousands of events in 3 s."""
        from kukeon_tpu import faults

        duration_ms = float(duration_ms)
        if not (0 < duration_ms <= MAX_CAPTURE_MS):
            raise ValueError(
                f"durationMs must be in (0, {MAX_CAPTURE_MS}]")
        faults.maybe_fail("profile.capture")
        name = f"capture-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
        rec = {
            "name": name,
            "path": os.path.join(self.base_dir, name),
            "state": "running",
            "startedAt": time.time(),
            "durationMs": duration_ms,
            "pythonTracer": python_tracer,
        }
        with self._lock:
            if self._active is not None:
                raise ProfileBusy(
                    f"capture {self._active['name']} is already running")
            self._active = rec
        threading.Thread(target=self._capture, args=(rec,), daemon=True,
                         name="profile-capture").start()
        return dict(rec)

    def _capture(self, rec: dict) -> None:
        try:
            import jax

            os.makedirs(rec["path"], exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = int(rec["pythonTracer"])
            jax.profiler.start_trace(rec["path"], profiler_options=options)
            try:
                time.sleep(rec["durationMs"] / 1000.0)
            finally:
                jax.profiler.stop_trace()
            rec["state"] = "done"
            rec["sizeBytes"] = _tree_size(rec["path"])
            if self._m_captures is not None:
                self._m_captures.inc(outcome="ok")
        except Exception as e:  # noqa: BLE001 — the spool must never wedge closed
            rec["state"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
            shutil.rmtree(rec["path"], ignore_errors=True)
            if self._m_captures is not None:
                self._m_captures.inc(outcome="error")
        finally:
            with self._lock:
                self._active = None
                if rec["state"] == "error":
                    self._failed.append(rec)
            self._prune()

    def _prune(self) -> None:
        """Keep only the newest K completed captures on disk."""
        try:
            entries = sorted(
                (e for e in os.scandir(self.base_dir) if e.is_dir()),
                key=lambda e: e.stat().st_mtime, reverse=True,
            )
        except OSError:
            return
        for stale in entries[self.keep:]:
            shutil.rmtree(stale.path, ignore_errors=True)

    def list(self) -> list[dict]:
        """Newest-first capture records: the running one (if any), recent
        failures, then completed captures read from the spool dir."""
        with self._lock:
            out = [dict(self._active)] if self._active is not None else []
            out.extend(dict(r) for r in reversed(self._failed))
        try:
            entries = sorted(
                (e for e in os.scandir(self.base_dir) if e.is_dir()),
                key=lambda e: e.stat().st_mtime, reverse=True,
            )
        except OSError:
            entries = []
        active_name = out[0]["name"] if out and out[0]["state"] == "running" \
            else None
        for e in entries:
            if e.name == active_name:
                continue
            out.append({
                "name": e.name,
                "path": e.path,
                "state": "done",
                "startedAt": e.stat().st_mtime,
                "sizeBytes": _tree_size(e.path),
            })
        return out


def _tree_size(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total
