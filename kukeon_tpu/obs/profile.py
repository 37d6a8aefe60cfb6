"""Roofline profiling: per-program timers, per-layer cost profiles, and
the engine step flight recorder.

The device/ layer (PR 4) answers "did it compile again"; this module
answers "where does the device time go" — three instruments deep:

- :class:`ProgramTimers` — dispatch counts, wall-time histograms, and
  token rates for every jitted engine program, plus static
  ``cost_analysis()`` FLOPs/bytes pulled at compile time. Scrape-time
  collectors derive roofline gauges from them: per-program MFU
  (``kukeon_program_mfu``) and HBM bandwidth utilization
  (``kukeon_program_membw_util``). Timing is settled inside the engine's
  counted ``_fetch`` seam only — a dispatch leaves a pending mark, and
  the next blocking readback (which the decode budget already pays for)
  retires every mark whose output is ready. Zero new device→host syncs:
  the host-sync budget tests pass unchanged with timers armed.
- :func:`profile_layers` — lowers each transformer layer's forward
  individually at prefill and decode shapes, recording cost-analysis
  FLOPs/bytes and measured wall time per layer. The persisted artifact
  (serving/tuning.py) is the direct input to pipeline-split placement:
  segmenting on measured per-layer cost instead of "layers are equal".
- :class:`FlightRecorder` — a bounded lock-disciplined ring of
  engine-loop step records (occupancy, chunk size, tokens, per-program
  wall times, transfer counts, preemptions, seated trace ids) behind
  ``GET /v1/timeline`` — "what was the engine doing in the 5s before
  the alert fired", reconstructable after the fact.

jax is imported lazily (function scope) throughout: the obs package
stays importable — and the timers/recorder fully testable — without an
accelerator runtime.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Iterable

from kukeon_tpu import sanitize

# The engine's seven jitted programs (ServingEngine._build_programs).
# kukelint KUKE015 requires every wrap() there to register with this
# seam; the names here are the timer-label vocabulary — distinct from
# the coarse prefill|insert|decode compile labels, which bench.py and
# the compile-flat tests consume and which must not change.
PROGRAMS = (
    "prefill",
    "prefill_ext",
    "insert",
    "decode_chunk",
    "gather_block",
    "insert_paged",
    "decode_chunk_paged",
)

# device_kind, exactly as the installed runtime reports it (checked by
# describing each topology with jax.experimental.topologies) ->
# (peak dense bf16 FLOP/s, peak HBM bytes/s) of one device. Source: the
# Google Cloud TPU documentation's system-architecture page of each
# generation ("TPU v5e": 197 TFLOP/s, 819 GB/s; "TPU v5p": 459 TFLOP/s,
# 2765 GB/s; "TPU v6e": 918 TFLOP/s, 1640 GB/s). Only generations where
# one JAX device is one chip are listed. A TPU that is not here gets NO
# utilization gauges (and the scrape says why) — never a made-up peak.
PEAKS_BY_DEVICE_KIND: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
}


def device_peaks() -> tuple[tuple[float, float] | None, str]:
    """((peak FLOP/s, peak HBM bytes/s) | None, reason) for device 0.

    None means "no utilization can be stated": on a non-TPU backend
    (reason empty — the families are simply declared empty, as the HBM
    families are), and on a TPU whose ``device_kind`` is not in
    :data:`PEAKS_BY_DEVICE_KIND` (reason names the kind, and rides the
    families' HELP text on the scrape)."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        return None, ""
    peaks = PEAKS_BY_DEVICE_KIND.get(d.device_kind)
    if peaks is None:
        return None, (f"no published peak for device_kind "
                      f"{d.device_kind!r} in obs/profile.py "
                      "PEAKS_BY_DEVICE_KIND")
    return peaks, ""


def cost_summary(compiled) -> tuple[float, float] | None:
    """(flops, bytes accessed) from a compiled executable's
    ``cost_analysis()`` dict; None when the backend reports nothing
    usable."""
    try:
        d = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — optional analysis, never a failure
        return None
    if not isinstance(d, dict):
        return None
    try:
        flops = float(d.get("flops", 0.0))
        nbytes = float(d.get("bytes accessed", 0.0))
    except (TypeError, ValueError):
        return None
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return flops, nbytes


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
    """Per-jitted-program roofline telemetry.

    Families (all labelled ``program=`` from :data:`PROGRAMS`):

    - ``kukeon_program_dispatch_total`` — dispatches.
    - ``kukeon_program_seconds`` — wall time per settled dispatch.
    - ``kukeon_program_tokens_total`` — tokens the program processed.
    - ``kukeon_program_flops`` / ``kukeon_program_hbm_bytes`` — static
      per-dispatch cost from ``cost_analysis()`` at compile time.
    - ``kukeon_program_mfu`` / ``kukeon_program_membw_util`` — derived
      at scrape time: achieved FLOP/s (bytes/s) over the device peak,
      clamped to 1.0.

    Timing protocol: the engine's ``_TrackedJit`` wrapper calls
    ``track(program).dispatched(t0, out)`` after each dispatch (async —
    nothing has executed yet), and the engine's ``_fetch`` calls
    :meth:`settle` right after its blocking readback. Device execution
    is in dispatch order, so everything enqueued before the fetched
    array is complete by then; readiness is probed non-blockingly and
    unready marks simply wait for the next fetch. The measured wall
    time therefore includes device queue wait — an overestimate that
    can only LOWER the derived MFU, never inflate it.
    """

    def __init__(self, registry, peaks: tuple[float, float] | None = None):
        self._registry = registry
        self._peaks = peaks
        self._lock = sanitize.lock("ProgramTimers._lock", hot=True)
        self._dispatches: dict[str, int] = {}     # guarded-by: _lock
        self._settled: dict[str, int] = {}        # guarded-by: _lock
        self._busy_s: dict[str, float] = {}       # guarded-by: _lock
        self._tokens: dict[str, int] = {}         # guarded-by: _lock
        self._costs: dict[str, tuple[float, float]] = {}  # guarded-by: _lock
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
        self._m_flops = registry.gauge(
            "kukeon_program_flops",
            "Static per-dispatch FLOPs from compile-time cost_analysis "
            "(0 until the program compiles on a reporting backend).",
            labels=("program",))
        self._m_bytes = registry.gauge(
            "kukeon_program_hbm_bytes",
            "Static per-dispatch bytes accessed from compile-time "
            "cost_analysis.",
            labels=("program",))
        registry.register_collector(self._collect)

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

    def set_cost(self, program: str, flops: float, nbytes: float) -> None:
        """Record a program's static per-dispatch cost (compile time)."""
        with self._lock:
            self._costs[program] = (float(flops), float(nbytes))
        self._m_flops.set(float(flops), program=program)
        self._m_bytes.set(float(nbytes), program=program)

    def note_cost(self, program: str, compiled) -> None:
        """``set_cost`` from a compiled executable's cost_analysis; a
        backend that reports nothing leaves the gauges at zero."""
        got = cost_summary(compiled)
        if got is not None:
            self.set_cost(program, got[0], got[1])

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

    # --- derived views -----------------------------------------------------

    def _utilization(self) -> tuple[dict[str, tuple[float, float]], str]:
        """({program: (mfu, membw_util)}, why-empty) over settled
        dispatches, clamped to [0, 1]: achieved = static per-dispatch cost
        x settled count / measured busy seconds, over the device's
        published peak. No published peak (:func:`device_peaks`) -> no
        entries, with the reason."""
        peaks, why = (self._peaks, "") if self._peaks else device_peaks()
        out: dict[str, tuple[float, float]] = {}
        if peaks is None:
            return out, why
        peak_flops, peak_bw = peaks
        with self._lock:
            for program, (flops, nbytes) in self._costs.items():
                n = self._settled.get(program, 0)
                busy = self._busy_s.get(program, 0.0)
                if n <= 0 or busy <= 0.0:
                    continue
                out[program] = (
                    min(1.0, (flops * n) / (busy * peak_flops)),
                    min(1.0, (nbytes * n) / (busy * peak_bw)),
                )
        return out, why

    def _collect(self) -> Iterable[object]:
        util, why = self._utilization()
        absent = f" ABSENT: {why}." if why else ""
        yield ("kukeon_program_mfu", "gauge",
               "Model FLOPs utilization per program: static FLOPs x "
               "settled dispatches / (measured busy seconds x device "
               "peak FLOP/s), clamped to 1." + absent,
               [({"program": p}, mfu) for p, (mfu, _bw) in
                sorted(util.items())])
        yield ("kukeon_program_membw_util", "gauge",
               "HBM bandwidth utilization per program: bytes accessed x "
               "settled dispatches / (busy seconds x peak bytes/s), "
               "clamped to 1." + absent,
               [({"program": p}, bw) for p, (_mfu, bw) in
                sorted(util.items())])

    def snapshot(self) -> dict[str, dict[str, float | None]]:
        """Per-program roofline summary for bench artifacts and step
        records: dispatches, settled count, busy seconds, tokens, static
        cost, and derived MFU/bandwidth utilization."""
        util, _why = self._utilization()
        out: dict[str, dict[str, float | None]] = {}
        with self._lock:
            programs = (set(self._dispatches) | set(self._costs)
                        | set(self._tokens))
            for p in sorted(programs):
                flops, nbytes = self._costs.get(p, (0.0, 0.0))
                mfu, bw = util.get(p, (None, None))
                out[p] = {
                    "dispatches": self._dispatches.get(p, 0),
                    "settled": self._settled.get(p, 0),
                    "busy_s": round(self._busy_s.get(p, 0.0), 6),
                    "tokens": self._tokens.get(p, 0),
                    "flops": flops,
                    "hbm_bytes": nbytes,
                    "mfu": None if mfu is None else round(mfu, 6),
                    "membw_util": None if bw is None else round(bw, 6),
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


# --- per-layer cost profiler -------------------------------------------------

LAYER_PROFILE_SCHEMA = "kukeon-layer-profile/v1"


def _time_compiled(fn, args, reps: int = 3) -> float:
    """Best-of-``reps`` wall seconds for one executed call (post-warmup,
    blocked to completion) — the cheapest honest point measurement."""
    best = None
    for _ in range(max(1, reps)):
        t0 = time.monotonic()
        out = fn(*args)
        leaf = _first_device_leaf(out)
        if leaf is not None:
            leaf.block_until_ready()
        dt = time.monotonic() - t0
        best = dt if best is None else min(best, dt)
    return float(best or 0.0)


def profile_layers(params, cfg, mesh=None, *, prefill_len: int = 64,
                   decode_batch: int = 8, measure: bool = True,
                   reps: int = 3) -> dict:
    """Per-component roofline profile of a llama model: embed, each
    transformer layer, and the LM head, each lowered INDIVIDUALLY at a
    prefill shape ``[1, prefill_len]`` and a decode shape
    ``[decode_batch, 1]``, recording cost-analysis FLOPs/bytes and (with
    ``measure=True``) executed wall time.

    The whole-model reference cost is taken from a scan-free composition
    of the same components (XLA's cost analysis cannot see a while
    loop's trip count, so scanning would under-count the stack) — the
    per-layer FLOPs sum matches it within the 5% acceptance bound by
    construction of the lowering, not by luck.

    Failures degrade, never crash: a component whose lowering (or the
    armed ``profile.layers`` fault point) raises contributes an
    ``error`` entry and profiling continues. The caller decides whether
    a partial profile is worth persisting (``result["errors"]``).
    """
    import jax
    import jax.numpy as jnp

    from kukeon_tpu import faults
    from kukeon_tpu.models import llama

    n_layers = int(cfg.num_layers)
    hidden = int(cfg.hidden_size)
    prefill_len = max(1, int(prefill_len))
    decode_batch = max(1, int(decode_batch))

    shapes = (
        ("prefill", (1, prefill_len)),
        ("decode", (decode_batch, 1)),
    )

    def _embed_fn(tokens):
        return llama._embed(params, tokens, cfg.dtype)

    def _head_fn(x):
        h = llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return llama._logits(params, cfg, h)

    def _layer_fn(i):
        w = jax.tree.map(lambda a: a[i], params["layers"])

        def fn(x, positions):
            return llama.transformer_block(x, w, cfg, positions)
        return fn

    def _whole_fn(tokens, positions):
        x = llama._embed(params, tokens, cfg.dtype)
        for i in range(n_layers):
            w = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x = llama.transformer_block(x, w, cfg, positions)
        return _head_fn(x)

    def _args_for(name: str, B: int, S: int):
        tokens = jnp.zeros((B, S), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = jnp.zeros((B, S, hidden), cfg.dtype)
        if name == "embed":
            return (tokens,)
        if name == "head":
            return (x,)
        if name == "model":
            return (tokens, positions)
        return (x, positions)

    def _profile_one(name: str, fn) -> dict:
        entry: dict[str, Any] = {"name": name}
        for shape_name, (B, S) in shapes:
            faults.maybe_fail("profile.layers")
            jitted = jax.jit(fn)
            args = _args_for(name, B, S)
            compiled = jitted.lower(*args).compile()
            got = cost_summary(compiled)
            rec = {"flops": got[0] if got else 0.0,
                   "bytes": got[1] if got else 0.0}
            if measure:
                _time_compiled(jitted, args, reps=1)   # warmup / cache prime
                rec["wall_s"] = round(_time_compiled(jitted, args, reps), 6)
            entry[shape_name] = rec
        return entry

    components: list[dict] = []
    errors = 0
    plan = [("embed", _embed_fn)]
    plan += [(f"layer{i}", _layer_fn(i)) for i in range(n_layers)]
    plan += [("head", _head_fn)]
    for name, fn in plan:
        try:
            components.append(_profile_one(name, fn))
        except Exception as e:  # noqa: BLE001 — a partial profile beats a dead cell
            errors += 1
            components.append(
                {"name": name, "error": f"{type(e).__name__}: {e}"})

    model_flops = model_bytes = 0.0
    try:
        compiled = jax.jit(_whole_fn).lower(
            *_args_for("model", 1, prefill_len)).compile()
        got = cost_summary(compiled)
        if got is not None:
            model_flops, model_bytes = got
    except Exception as e:  # noqa: BLE001 — reference cost is advisory
        errors += 1
        components.append({"name": "model", "error":
                           f"{type(e).__name__}: {e}"})

    return {
        "schema": LAYER_PROFILE_SCHEMA,
        "num_layers": n_layers,
        "prefill_len": prefill_len,
        "decode_batch": decode_batch,
        "model_flops": model_flops,
        "model_bytes": model_bytes,
        "components": components,
        "errors": errors,
    }
