"""Fault-injection harness: named failure points, armed via environment.

The runtime's resilience behaviors (load shedding, deadline expiry, engine
recovery, watchdog trip, crash-atomic checkpointing) all respond to failures
that are hard to *time* in a test — a wedged chip, a kill mid-save, a dying
HTTP handler. This module turns each of them into a named seam:

    from kukeon_tpu import faults
    faults.maybe_fail("engine.decode")          # raises iff armed

Arming syntax (``KUKEON_FAULTS`` env var)::

    KUKEON_FAULTS=point:prob[:count][,point2:prob2[:count2]]

- ``point``  — the seam name passed to :func:`maybe_fail` (exact match).
- ``prob``   — firing probability per hit, ``1`` meaning always.
- ``count``  — optional cap on total fires for this point (e.g.
  ``engine.decode:1:2`` fails the first two decode dispatches, then
  passes). Without it the point fires forever.

Contract:

- **Unarmed is free.** With ``KUKEON_FAULTS`` unset/empty, :func:`maybe_fail`
  is a single dict lookup and returns immediately — no parsing, no locking,
  no allocation. Production code can leave the calls in hot-ish paths
  (engine dispatch, host transfers) without a measurable tax; the guard
  test in tests/test_faults.py pins this.
- **Env changes take effect immediately.** The parsed table is cached
  keyed on the raw env string, so tests may flip ``KUKEON_FAULTS`` between
  (or within) tests without touching module state; the conftest fixture
  clears the env and calls :func:`reset` around every test.
- Fires are counted in :data:`stats` so tests can assert a point actually
  triggered (a fault test whose seam was renamed must fail, not silently
  pass).
"""

from __future__ import annotations

import os
import random
import threading

ENV = "KUKEON_FAULTS"

# Every fault point threaded through the codebase, declared here so the
# observability layer can expose a ``kukeon_faults_fired_total{point=...}``
# sample for each one (zero when never fired) and the guard test in
# tests/test_obs.py can grep call sites against this list — a new
# ``maybe_fail("x.y")`` that is not declared here fails CI, so fault
# points can't ship unobservable.
POINTS = (
    "engine.prefill",
    "engine.decode",
    "engine.fetch",
    "engine.upload",
    "kv.alloc",
    "kv.handoff",
    "cell.http",
    "gateway.spill",
    "scaler.tick",
    "alerts.webhook",
    "checkpoint.save",
    "checkpoint.load",
    "checkpoint.stream",
    "devices.probe_wedged",
    "profile.capture",
)


class FaultInjected(RuntimeError):
    """Raised by an armed fault point (the injected failure)."""


class _Point:
    __slots__ = ("prob", "remaining")

    def __init__(self, prob: float, remaining: int | None):
        self.prob = prob
        self.remaining = remaining   # None = unlimited


_lock = threading.Lock()
_cached_spec: str | None = None          # raw env value the table came from
_points: dict[str, _Point] = {}

# point -> number of times it fired (survives re-parses; reset() clears it).
stats: dict[str, int] = {}


def _parse(spec: str) -> dict[str, _Point]:
    points: dict[str, _Point] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        if not bits[0]:
            raise ValueError(f"{ENV}: empty fault point in {part!r}")
        prob = float(bits[1]) if len(bits) > 1 and bits[1] else 1.0
        count = int(bits[2]) if len(bits) > 2 and bits[2] else None
        points[bits[0]] = _Point(prob, count)
    return points


def active() -> bool:
    """True when any fault spec is armed."""
    return bool(os.environ.get(ENV))


def fired(point: str) -> int:
    """How many times ``point`` has fired since the last :func:`reset`."""
    return stats.get(point, 0)


def reset() -> None:
    """Drop the parsed table and fire counts (test isolation seam)."""
    global _cached_spec
    with _lock:
        _cached_spec = None
        _points.clear()
        stats.clear()


def maybe_fail(point: str, exc: type[BaseException] = FaultInjected,
               msg: str | None = None) -> None:
    """Raise ``exc`` iff ``point`` is armed via ``KUKEON_FAULTS`` and fires.

    The unarmed path is a single env lookup; see module docstring.
    """
    spec = os.environ.get(ENV)
    if not spec:
        return
    global _cached_spec
    with _lock:
        if spec != _cached_spec:
            _points.clear()
            _points.update(_parse(spec))
            _cached_spec = spec
        p = _points.get(point)
        if p is None:
            return
        if p.remaining is not None and p.remaining <= 0:
            return
        if p.prob < 1.0 and random.random() >= p.prob:
            return
        if p.remaining is not None:
            p.remaining -= 1
        stats[point] = stats.get(point, 0) + 1
    raise exc(msg or f"injected fault at {point!r} ({ENV}={spec})")
