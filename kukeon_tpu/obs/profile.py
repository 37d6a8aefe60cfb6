"""Per-program dispatch timers and the engine step flight recorder.

The device/ layer (PR 4) answers "did it compile again"; this module
counts what the engine's jitted programs did, two instruments deep:

- :class:`ProgramTimers` — dispatch counts, wall-time histograms, and
  token counts for every jitted engine program. Timing is settled inside
  the engine's counted ``_fetch`` seam only — a dispatch leaves a pending
  mark, and the next blocking readback (which the decode budget already
  pays for) retires every mark whose output is ready. Zero new
  device→host syncs: the host-sync budget tests pass unchanged with
  timers armed. The settled seconds are a host clock that includes the
  device queue: they order programs by cost, they are not device times
  (rooflines come from the benchmark's device trace, benchmark/README.md).
- :class:`FlightRecorder` — a bounded lock-disciplined ring of
  engine-loop step records (occupancy, chunk size, tokens, per-program
  wall times, transfer counts, preemptions, seated trace ids) behind
  ``GET /v1/timeline`` — "what was the engine doing in the 5s before
  the alert fired", reconstructable after the fact.

No jax import: the obs package stays importable — and the timers/recorder
fully testable — without an accelerator runtime.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any

from kukeon_tpu import sanitize

# The engine's seven jitted programs (ServingEngine._build_programs).
# kukelint KUKE015 requires every wrap() there to register with this
# seam; the names here are the timer-label vocabulary — distinct from
# the coarse prefill|insert|decode compile labels, which the benchmark's
# compiles_in_window and the compile-flat tests consume and which must
# not change.
PROGRAMS = (
    "prefill",
    "prefill_ext",
    "insert",
    "decode_chunk",
    "gather_block",
    "insert_paged",
    "decode_chunk_paged",
)

def _first_device_leaf(out: Any) -> Any | None:
    """First leaf in a (possibly nested) program output that looks like a
    device array — the readiness probe target for deferred timing."""
    stack = [out]
    while stack:
        x = stack.pop()
        if hasattr(x, "block_until_ready"):
            return x
        if isinstance(x, (tuple, list)):
            stack.extend(x)
        elif isinstance(x, dict):
            stack.extend(x.values())
    return None


class _ProgramTimer:
    """Per-program dispatch marks. ``dispatched`` and ``settle`` both run
    on the engine driver thread only (dispatch sites and the ``_fetch``
    seam), so the pending deque needs no lock; the shared accumulators
    the scrape thread reads live in the parent under its lock."""

    # Marks outliving this many newer dispatches were lost to a dropped
    # readiness probe; cap the deque so they can never accumulate.
    MAX_PENDING = 8

    def __init__(self, owner: "ProgramTimers", program: str):
        self._owner = owner
        self.program = program
        self._pending: deque[tuple[float, Any]] = deque(maxlen=self.MAX_PENDING)

    def dispatched(self, t0: float, out: Any) -> None:
        """Record a dispatch that started at ``t0`` whose result is
        ``out`` — counted now, timed when a later ``settle`` finds the
        output ready."""
        self._owner._note_dispatch(self.program)
        leaf = _first_device_leaf(out)
        if leaf is not None:
            self._pending.append((t0, leaf))

    def settle(self, now: float) -> None:
        while self._pending:
            t0, leaf = self._pending[0]
            try:
                ready = bool(leaf.is_ready()) if hasattr(leaf, "is_ready") \
                    else True
            except Exception:  # noqa: BLE001 — donated buffers raise: consumed == done
                ready = True
            if not ready:
                break
            self._pending.popleft()
            self._owner._note_settled(self.program, max(0.0, now - t0))


class ProgramTimers:
    """Per-jitted-program dispatch telemetry.

    Families (all labelled ``program=`` from :data:`PROGRAMS`):

    - ``kukeon_program_dispatch_total`` — dispatches.
    - ``kukeon_program_seconds`` — wall time per settled dispatch.
    - ``kukeon_program_tokens_total`` — tokens the program processed.

    Timing protocol: the engine's ``_TrackedJit`` wrapper calls
    ``track(program).dispatched(t0, out)`` after each dispatch (async —
    nothing has executed yet), and the engine's ``_fetch`` calls
    :meth:`settle` right after its blocking readback. Device execution
    is in dispatch order, so everything enqueued before the fetched
    array is complete by then; readiness is probed non-blockingly and
    unready marks simply wait for the next fetch. The measured wall
    time therefore includes device queue wait.
    """

    def __init__(self, registry):
        self._registry = registry
        self._lock = sanitize.lock("ProgramTimers._lock", hot=True)
        self._dispatches: dict[str, int] = {}     # guarded-by: _lock
        self._settled: dict[str, int] = {}        # guarded-by: _lock
        self._busy_s: dict[str, float] = {}       # guarded-by: _lock
        self._tokens: dict[str, int] = {}         # guarded-by: _lock
        self._timers: dict[str, _ProgramTimer] = {}
        self._m_dispatch = registry.counter(
            "kukeon_program_dispatch_total",
            "Jitted program dispatches, by engine program.",
            labels=("program",))
        self._m_seconds = registry.histogram(
            "kukeon_program_seconds",
            "Wall time per settled program dispatch (includes device "
            "queue wait), by program.",
            labels=("program",))
        self._m_tokens = registry.counter(
            "kukeon_program_tokens_total",
            "Tokens processed (prompt rows prefetched, batch*k decoded), "
            "by program.",
            labels=("program",))

    # --- engine-facing seam ------------------------------------------------

    def track(self, program: str) -> _ProgramTimer:
        """The (engine-driver-thread) timer handle for one program; the
        ``timer=`` argument CompileTracker.wrap threads into _TrackedJit
        (kukelint KUKE015 requires every _build_programs wrap to pass
        one)."""
        t = self._timers.get(program)
        if t is None:
            t = self._timers[program] = _ProgramTimer(self, program)
        return t

    def settle(self) -> None:
        """Retire pending dispatch marks whose outputs are ready. Called
        from the engine's counted ``_fetch`` seam ONLY — right after a
        blocking readback the budget already paid for."""
        now = time.monotonic()
        for t in self._timers.values():
            t.settle(now)

    def note_tokens(self, program: str, n: int) -> None:
        if n <= 0:
            return
        with self._lock:
            self._tokens[program] = self._tokens.get(program, 0) + int(n)
        self._m_tokens.inc(int(n), program=program)

    # --- accumulators (driver thread writes, scrape thread reads) ----------

    def _note_dispatch(self, program: str) -> None:
        with self._lock:
            self._dispatches[program] = self._dispatches.get(program, 0) + 1
        self._m_dispatch.inc(program=program)

    def _note_settled(self, program: str, dt: float) -> None:
        with self._lock:
            self._settled[program] = self._settled.get(program, 0) + 1
            self._busy_s[program] = self._busy_s.get(program, 0.0) + dt
        self._m_seconds.observe(dt, program=program)

    # --- views --------------------------------------------------------------

    def snapshot(self) -> dict[str, dict[str, float]]:
        """Per-program summary for the step records: dispatches, settled
        count, busy seconds, tokens."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for p in sorted(set(self._dispatches) | set(self._tokens)):
                out[p] = {
                    "dispatches": self._dispatches.get(p, 0),
                    "settled": self._settled.get(p, 0),
                    "busy_s": round(self._busy_s.get(p, 0.0), 6),
                    "tokens": self._tokens.get(p, 0),
                }
        return out

    def busy_seconds(self) -> dict[str, float]:
        with self._lock:
            return dict(self._busy_s)


class FlightRecorder:
    """Bounded ring of engine-loop step records — the step timeline.

    The engine driver appends one small dict per working step
    (:meth:`record`); HTTP readers snapshot the newest N
    (:meth:`snapshot`). The ring is a preallocated circular list: memory
    is bounded at ``capacity`` records forever, overwritten (dropped)
    records are counted on ``kukeon_timeline_dropped_total``, and both
    sides take one short lock — green under KUKEON_SANITIZE=1 with
    ingest and readers hammering concurrently.
    """

    DEFAULT_CAPACITY = 512

    def __init__(self, capacity: int = DEFAULT_CAPACITY, registry=None):
        self.capacity = max(1, int(capacity))
        self._lock = sanitize.lock("FlightRecorder._lock", hot=True)
        self._ring: list[dict | None] = [None] * self.capacity  # guarded-by: _lock
        self._next_seq = 0   # guarded-by: _lock
        self._dropped = 0    # guarded-by: _lock
        self._m_dropped = None
        if registry is not None:
            self._m_dropped = registry.counter(
                "kukeon_timeline_dropped_total",
                "Step records overwritten in the flight-recorder ring "
                "before any reader saw the window slide past them.")
            registry.gauge(
                "kukeon_timeline_depth",
                "Step records currently held in the flight-recorder "
                "ring (caps at its capacity).").set_function(
                lambda: float(len(self)))

    def record(self, rec: dict) -> int:
        """Append one step record; returns its sequence number. The
        record is stamped with ``seq`` and ``t`` (wall-clock seconds)
        here so every producer shares one schema spine."""
        rec = dict(rec)
        rec.setdefault("t", time.time())
        with self._lock:
            seq = self._next_seq
            self._next_seq = seq + 1
            rec["seq"] = seq
            idx = seq % self.capacity
            if self._ring[idx] is not None:
                self._dropped += 1
            self._ring[idx] = rec
        if self._m_dropped is not None and seq >= self.capacity:
            self._m_dropped.inc()
        return seq

    def snapshot(self, n: int | None = None) -> list[dict]:
        """The newest ``n`` (default: all held) step records, oldest
        first — the shape `kuke timeline` renders top-to-bottom."""
        with self._lock:
            end = self._next_seq
            held = min(end, self.capacity)
            want = held if n is None else max(0, min(int(n), held))
            out = [self._ring[s % self.capacity]
                   for s in range(end - want, end)]
        return [dict(r) for r in out if r is not None]

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return min(self._next_seq, self.capacity)
