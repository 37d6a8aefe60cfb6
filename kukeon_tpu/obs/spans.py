"""Host spans on the profiler's clock, and the engine loop's time by phase.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``: while a
``POST /v1/profile`` capture runs, the span lands on the capture's
``/host:CPU`` plane with its arguments as the event's stats, on the same time
base as the device planes; outside a capture it is a level check. There is no
switch: the spans are always in the code, and the profiler decides whether
they are recorded.

``LoopSpans`` is the engine's: its spans also add their ``time.monotonic``
duration to ``kukeon_engine_loop_seconds_total{phase}``, which partitions the
loop thread's wall time. ``engine.step`` is the parent of the five phases that
do a step's work; what it spends outside them is phase ``other``.

| span | arguments | counter phase |
| --- | --- | --- |
| ``cell.generate`` | request | - (above the engine) |
| ``engine.step`` | | ``other`` = its time less its children |
| ``engine.admit`` | free, queued | ``admit`` |
| ``engine.prefill_dispatch`` | request, slot, decoding, program, hit, cached, real, padded[, <kind>_rows, <kind>_slots] | - (inside ``admit``) |
| ``engine.decode_dispatch`` | k, active, live_rows[, <kind>_rows, <kind>_slots] | ``decode_dispatch`` |
| ``engine.fetch_first`` | n | ``fetch_first`` |
| ``engine.fetch_chunk`` | k, active | ``fetch_chunk`` |
| ``engine.emit`` | tokens | ``emit`` |
| ``engine.first_token`` | request | - (inside ``emit``) |
| ``engine.idle_wait`` | | ``idle_wait`` |

``<kind>_rows`` (``window_rows``, ``full_rows``) are on the spans of a family
whose layers hold several kinds of state (``models/kv_kinds.py``): the rows the
dispatched slots hold in one layer of each kind. A kind that holds state
without rows (a state-space layer's) gives ``<kind>_slots`` (``state_slots``):
the slots whose state the dispatch touches.

``decoding`` is the slots that were seated, each with a first token, when
the step that dispatched the prefill began to admit: their next chunk runs
behind it on the device. ``active`` is the slots the fetched chunk stepped.
With the device's module events they say how much of the slot-time spent
decoding went to other requests' prompts.

``request`` is the request's trace id (``req.trace.trace_id``), the identifier
``/v1/trace`` and ``/v1/timeline`` already use.
"""

from __future__ import annotations

import time

STEP = "engine.step"
# engine.step's children that are counter phases; with idle_wait and other
# they sum to the loop thread's wall time.
STEP_PHASES = ("admit", "decode_dispatch", "fetch_first", "fetch_chunk",
               "emit")
LOOP_PHASES = STEP_PHASES + ("idle_wait", "other")
_PHASE = {"engine." + p: p for p in STEP_PHASES + ("idle_wait",)}
_PHASE[STEP] = "other"


class Span:
    """One open span; ``set`` adds arguments known only once the work is
    under way (a prefill's token counts, the tokens a loop emitted)."""

    __slots__ = ("_loop", "_name", "_args", "_annotation", "_t0", "_outer")

    def __init__(self, loop: "LoopSpans | None", name: str, args: dict):
        # Only a span that is a counter phase keeps the loop's clock.
        self._loop = loop if name in _PHASE else None
        self._name, self._args = name, args

    def __enter__(self) -> "Span":
        # jax is loaded by whoever serves a model; the control plane imports
        # this package and must not pay for it (kukelint KUKE013).
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(self._name, **self._args)
        self._annotation.__enter__()
        loop = self._loop
        if loop is not None:
            if self._name == STEP:
                loop.step_s = {}
            self._outer, loop._inner = loop._inner, 0.0
            self._t0 = time.monotonic()
        return self

    def set(self, **args) -> None:
        self._annotation.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        loop = self._loop
        if loop is not None:
            seconds = time.monotonic() - self._t0
            # A phase is charged its self time, so that phases partition the
            # thread's time however they nest (a paged engine under page
            # pressure fetches a chunk inside decode_dispatch).
            loop._charge(_PHASE[self._name], seconds - loop._inner)
            loop._inner = self._outer + seconds
        self._annotation.__exit__(*exc)
        return False


def span(name: str, **args) -> Span:
    """A span with no counter behind it: for code above the engine loop."""
    return Span(None, name, args)


class LoopSpans:
    """The engine loop's spans and its wall time by phase. Driver thread
    only: one step is open at a time."""

    def __init__(self, registry):
        self._seconds = registry.counter(
            "kukeon_engine_loop_seconds_total",
            "Wall time of the engine loop's thread by phase; the phases "
            "partition it (other = engine.step outside its children).",
            labels=("phase",))
        for phase in LOOP_PHASES:       # every phase in the first scrape
            self._seconds.inc(0.0, phase=phase)
        self._inner = 0.0       # seconds of closed phases inside the open one
        self.step_s: dict[str, float] = {}   # the open step's seconds by phase

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def _charge(self, phase: str, seconds: float) -> None:
        seconds = max(0.0, seconds)
        self._seconds.inc(seconds, phase=phase)
        self.step_s[phase] = self.step_s.get(phase, 0.0) + seconds

    def host_s(self, step_seconds: float) -> dict[str, float]:
        """The open step's seconds by phase, for its flight-recorder record
        (``step_seconds``: the step's time so far; what its closed phases
        leave of it is ``other``)."""
        out = {p: round(s, 6) for p, s in self.step_s.items()}
        out["other"] = round(max(0.0, step_seconds - sum(self.step_s.values())), 6)
        return out
