"""Model-serving cell entrypoint: HTTP front-end over the ServingEngine.

The in-tree serving workload the runner materializes for ``CellSpec.model``
(BASELINE north star: "an in-tree JetStream (JAX/XLA) inference cell"). The
runner grants chips via TPU_VISIBLE_DEVICES before exec; this process builds
the mesh over whatever devices JAX exposes and serves:

  GET  /v1/health    -> {"status": "ok", ...}  (the reconciler's health seam)
  GET  /healthz      -> liveness (200 while the process can answer at all)
  GET  /readyz       -> readiness (503 until warmup completes, while
                        draining, and after the TPU watchdog trips)
  POST /drain        -> stop admitting, finish in-flight, then exit cleanly
  GET  /v1/stats     -> slots/queue/throughput counters (a JSON view over
                        the same obs registry /metrics scrapes)
  GET  /metrics      -> Prometheus text exposition: engine latency
                        histograms (TTFT/inter-token/e2e/queue-wait),
                        the loop's seconds by phase, prefill tokens by
                        kind, shed/timeout/watchdog/fault counters,
                        slot/queue gauges (kukeon_tpu/obs)
  GET  /v1/trace?n=K -> newest K per-request trace spans (lifecycle events
                        + per-phase durations summing to e2e);
                        ?request_id=N pulls one request's span exactly
  POST /v1/profile   -> {"durationMs": N} starts a single-flight
                        jax.profiler capture into KUKEON_PROFILE_DIR
                        (409 while one runs); the host side is the engine
                        loop's own spans (obs/spans.py), Python frames only
                        with "pythonTracer": true; GET /v1/profile lists
                        captures
  POST /v1/generate  -> {"promptTokens": [...] | "prompt": "text",
                         "maxNewTokens": N, "temperature": T,
                         "deadlineS": D, ...}
                        => {"tokens": [...], "text": "..."}
  POST /v1/kv/export -> generate-shaped JSON body in, binary KV handoff
                        block out (prefill only — no decode slot consumed);
                        the disaggregated gateway's first hop
  POST /v1/kv/import -> binary KV handoff block in, the continuation out
                        (JSON, or ndjson when the header says stream); the
                        imported request seats straight into a decode slot
                        via the paged insert program, never re-prefilling

Resilience: admission is bounded (``--max-pending`` -> 429 + Retry-After),
requests carry deadlines (``--deadline-s`` default, per-request
``deadlineS``), and a TPU watchdog (KUKEON_WATCHDOG_S) detects a stuck
engine step, confirms with devices.probe_tpu_in_process (this process
holds the chip; no second process could open it), and exits nonzero so
the runner's restart policy recovers the cell on its own chip grant.

Tokenization: checkpoint-less engines (random init, dev/e2e) use a byte
tokenizer (id = byte + 1); real deployments pass a HF tokenizer name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from kukeon_tpu import faults, sanitize
from kukeon_tpu.obs import (
    FlightRecorder,
    ProfileBusy,
    ProfileSpool,
    Registry,
    SloObjectives,
    SloTracker,
    device_memory_collector,
    expo,
)
from kukeon_tpu.obs import spans
from kukeon_tpu.obs import trace as obs_trace
from kukeon_tpu.serving.engine import DeadlineExceeded, RejectedError

MODELS = {}
EMBEDDING_MODELS = {}

# Process birth (well, module import — the runner execs `python -m
# kukeon_tpu.runtime.serving_cell`, so they coincide in production):
# the zero point for the cold-start phase breakdown finish_boot exports.
_PROC_T0 = time.monotonic()

# Exit code for a watchdog-confirmed wedged TPU runtime: nonzero so the
# runner's restart policy (always / on-failure) restarts the cell, distinct
# from generic crashes so the operator can grep for it in `kuke get` reasons.
WEDGED_EXIT_CODE = 86

DRAIN_TIMEOUT_ENV = "KUKEON_DRAIN_TIMEOUT_S"
WATCHDOG_ENV = "KUKEON_WATCHDOG_S"
WATCHDOG_PROBE_TIMEOUT_ENV = "KUKEON_WATCHDOG_PROBE_TIMEOUT_S"


@sanitize.guard_class
class LifecycleMixin:
    """Readiness/drain lifecycle shared by both cell flavors.

    States: warming up (unready) -> ready -> draining (unready, in-flight
    finishing) -> drained. The watchdog flips unready via mark_unready
    before exiting. Everything here is advisory for direct (non-HTTP) cell
    use; the HTTP handler is where admission is enforced.

    Lock hierarchy: ``_drain_lock`` serializes the drain state machine
    (``draining`` flips exactly once), ``_inflight_lock`` guards the HTTP
    in-flight count — they never nest. Under ``KUKEON_SANITIZE=1`` both
    are kukesan recording proxies and the guarded-by contract below is
    enforced on every write.
    """

    def _init_lifecycle(self):
        self._ready = sanitize.event("LifecycleMixin._ready")
        self.unready_reason: str | None = "warming up"
        # Guarded attrs are assigned BEFORE their locks exist: kukesan's
        # __setattr__ hook then skips them even when a subclass constructs
        # without a wrapped __init__ (no guard lock to interrogate yet).
        self.draining = False   # guarded-by: _drain_lock
        self._drain_lock = sanitize.lock("LifecycleMixin._drain_lock")
        self.drained = sanitize.event("LifecycleMixin.drained")
        self._inflight = 0      # guarded-by: _inflight_lock
        self._inflight_lock = sanitize.lock("LifecycleMixin._inflight_lock")
        # Drain wake signal (shares _inflight_lock): _inflight_dec notifies
        # when the HTTP in-flight count hits zero, so the drain loop wakes
        # the moment the last request finishes instead of sleep-polling
        # _idle() at 50ms (the same condition-over-poll fix the engine
        # loop got). The timed wait below doubles as the poll for the
        # engine-side half of _idle(), which this condition cannot see.
        self._inflight_zero = sanitize.condition(
            self._inflight_lock, name="LifecycleMixin._inflight_zero")
        # main() points this at server.shutdown so a finished drain unblocks
        # serve_forever and the process exits 0.
        self.on_drained = None

    def _init_cell_obs(self, registry: Registry, kind: str) -> None:
        """Cell-level observability shared by both cell flavors: lifecycle
        gauges (scrape-time callables — zero cost between scrapes) plus
        the fault-injection fire-count family, all on the one registry
        ``GET /metrics`` renders."""
        self.registry = registry
        registry.gauge("kukeon_cell_info",
                       "Static cell identity (value always 1).",
                       labels=("model", "kind")).set(
            1, model=self.model_name, kind=kind)
        registry.gauge("kukeon_cell_uptime_seconds",
                       "Seconds since cell construction.").set_function(
            lambda: time.time() - self.started_at)
        registry.gauge("kukeon_cell_ready",
                       "1 while admitting requests (readyz).").set_function(
            lambda: 1.0 if self.readiness()[0] else 0.0)
        registry.gauge("kukeon_cell_draining",
                       "1 while a drain is in progress.").set_function(
            lambda: 1.0 if self.draining else 0.0)
        registry.gauge("kukeon_cell_http_inflight",
                       "HTTP requests currently being served.").set_function(
            lambda: float(self._inflight))
        # Pre-declare the watchdog families so a scrape sees them at zero
        # even before (or without) an EngineWatchdog — the watchdog's own
        # get-or-create then lands on these same counters.
        registry.counter(
            "kukeon_watchdog_probes_total",
            "TPU runtime probes fired after an engine stall.",
            labels=("verdict",))
        registry.counter(
            "kukeon_watchdog_trips_total",
            "Wedged verdicts (the cell exits for restart right after).")
        registry.register_collector(expo.faults_collector)
        # Device telemetry on every cell flavor (register_collector dedupes,
        # so the decoder cell — whose engine already registered the same
        # collector on the shared registry — emits the families once).
        registry.register_collector(device_memory_collector)
        # On-demand profiler spool behind POST/GET /v1/profile: single-
        # flight jax.profiler captures into KUKEON_PROFILE_DIR, keep-last-K.
        self.profiler = ProfileSpool(registry=registry)
        # Step flight recorder behind GET /v1/timeline: the decoder cell
        # aliases its engine's ring (one ring, one dropped-counter family);
        # flavors without an engine-side recorder get a cell-local one
        # (the embedding cell records one entry per embed batch).
        # NB: an explicit None check — FlightRecorder defines __len__, so
        # an (empty) engine ring is falsy and `or` would shadow it with a
        # second ring nobody writes to.
        engine_rec = getattr(getattr(self, "engine", None), "recorder", None)
        self.recorder = (engine_rec if engine_rec is not None
                         else FlightRecorder(registry=registry))

    def mark_ready(self):
        self.unready_reason = None
        self._ready.set()

    def mark_unready(self, reason: str):
        self.unready_reason = reason
        self._ready.clear()

    def readiness(self) -> tuple[bool, str | None]:
        if self.draining:
            return False, "draining"
        if not self._ready.is_set():
            return False, self.unready_reason or "not ready"
        return True, None

    def check_admission(self):
        """Raise RejectedError while the cell must not take new requests.
        Queue-full shedding lives in the engine; this is the lifecycle
        layer (warming up / draining / watchdog-tripped)."""
        ok, why = self.readiness()
        if not ok:
            raise RejectedError(f"not admitting requests: {why}",
                                retry_after_s=5.0)

    def _inflight_inc(self):
        with self._inflight_lock:
            self._inflight += 1

    def _inflight_dec(self):
        with self._inflight_lock:
            self._inflight -= 1
            if self._inflight == 0:
                # Wake a drain loop parked on the condition NOW — the
                # last in-flight request completing is exactly the event
                # it is waiting for.
                self._inflight_zero.notify_all()

    def _idle(self) -> bool:
        """No in-flight HTTP requests (subclasses add engine occupancy)."""
        with self._inflight_lock:
            return self._inflight == 0

    def begin_drain(self) -> bool:
        """Stop admitting, finish in-flight work, then report drained (and
        fire on_drained, which in main() shuts the HTTP server down).
        Idempotent; returns False if a drain was already running."""
        with self._drain_lock:
            if self.draining:
                return False
            self.draining = True
        self.mark_unready("draining")
        threading.Thread(target=self._drain_loop, daemon=True,
                         name="cell-drain").start()
        return True

    def _drain_loop(self):
        timeout = float(os.environ.get(DRAIN_TIMEOUT_ENV, "30") or 30)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and not self._idle():
            # Park on the inflight-zero condition instead of sleep-polling
            # (KUKE009 discipline): the last HTTP request's _inflight_dec
            # wakes the drain immediately; the bounded wait is the safety
            # net AND the poll tick for engine-side work the condition is
            # not signalled for (ServingCell._idle also watches
            # engine._requests).
            with self._inflight_zero:
                self._inflight_zero.wait(timeout=0.05)
        self._shutdown_engine()
        self.drained.set()
        if self.on_drained is not None:
            self.on_drained()

    def _shutdown_engine(self):
        pass


def _trailing_fffd(s: str) -> int:
    """Length of the run of U+FFFD replacement chars at the end of ``s``
    (the provisional decode of an incomplete multi-byte codepoint)."""
    n = 0
    while n < len(s) and s[-1 - n] == "�":
        n += 1
    return n


# --- KV handoff wire format (disaggregated serving) --------------------------
#
# One prefill's output travels prefill cell -> gateway -> decode cell as a
# single binary body: a JSON header line (token, length, dtype, shape, byte
# counts, plus — on the import leg — the generation parameters), then the
# raw K rows, then the raw V rows. JSON-encoding multi-MB bf16 tensors
# would triple the bytes; this stays a flat memcpy on both ends.

KV_CONTENT_TYPE = "application/x-kukeon-kv"


def _kv_dtype(name: str):
    """numpy dtype from its string name, including the ml_dtypes families
    (bfloat16 & friends) jax checkpoints use."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def pack_kv(header: dict, k: np.ndarray, v: np.ndarray) -> bytes:
    """Serialize a KV block + header into the handoff wire format."""
    kb = np.ascontiguousarray(k).tobytes()
    vb = np.ascontiguousarray(v).tobytes()
    head = dict(header)
    head.update({
        "dtype": str(k.dtype), "shape": list(k.shape),
        "kBytes": len(kb), "vBytes": len(vb),
    })
    return json.dumps(head).encode() + b"\n" + kb + vb


def unpack_kv(body: bytes) -> tuple[dict, np.ndarray, np.ndarray]:
    """Parse the handoff wire format back into (header, k, v)."""
    nl = body.find(b"\n")
    if nl < 0:
        raise ValueError("KV body has no header line")
    header = json.loads(body[:nl])
    dtype = _kv_dtype(header["dtype"])
    shape = tuple(int(s) for s in header["shape"])
    kb, vb = int(header["kBytes"]), int(header["vBytes"])
    raw = body[nl + 1:]
    if len(raw) != kb + vb:
        raise ValueError(
            f"KV body truncated: header claims {kb + vb} tensor bytes, "
            f"got {len(raw)}")
    k = np.frombuffer(raw[:kb], dtype=dtype).reshape(shape)
    v = np.frombuffer(raw[kb:], dtype=dtype).reshape(shape)
    return header, k, v


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed path inside the checkout. The path is part of a cache entry's
# key, so every process of this checkout — cells under the daemon, the
# benchmark's cell, chip_smoke.py's two boots — must name the same
# directory or a second boot never hits; a machine that is new on every run
# has no $HOME worth caching in.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compilation_cache_dir() -> str:
    """The persistent XLA compilation cache directory in force:
    ``JAX_COMPILATION_CACHE_DIR`` when the operator set it, else the fixed
    in-checkout path."""
    return os.environ.get(CACHE_DIR_ENV) or _CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: the dominant cold-start cost after
    weight load is jit compilation; caching it on disk makes every boot
    after the first (same program shapes) start in seconds. Standard TPU
    serving practice (JetStream does the same).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no directory in code. JAX keys every entry by its own
    version and the backend's platform version (which embeds the libtpu
    build), so a rolled runtime misses instead of loading stale AOT
    artifacts; main()'s bust-and-retry covers an entry that keys
    identically but fails to deserialize."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        try:
            os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
        except OSError as e:
            print(f"serving-cell: compilation cache {_CHECKOUT_CACHE_DIR} "
                  f"cannot be set up ({e}); every boot will compile cold",
                  file=sys.stderr, flush=True)
            return
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def _bust_compilation_cache() -> bool:
    """Empty the cache directory in force; True if there was anything to
    remove. Last-resort self-heal for a corrupted cache entry that keys
    identically but fails to deserialize (crash-looping forever would be
    worse than one slow recompile)."""
    import shutil

    try:
        entries = list(os.scandir(compilation_cache_dir()))
    except OSError:
        return False
    for e in entries:
        if e.is_dir(follow_symlinks=False):
            shutil.rmtree(e.path, ignore_errors=True)
        else:
            try:
                os.unlink(e.path)
            except OSError:
                pass
    return bool(entries)


def _device_census() -> dict:
    """The /v1/stats device fields, as JAX reports them in THIS process —
    the one that holds the chip: what a client (chip_smoke.py, the gateway)
    can trust about where the model actually runs."""
    import jax

    devices = jax.devices()
    return {
        "devices": [str(d) for d in devices],
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
    }


# A model's family is the type of the config it registers
# (models/families.py). This set marked the second family before that and
# decides nothing now; launchers written against it still add to it.
MOE_MODELS = set()


def _register_models():
    from kukeon_tpu.models import (bert, llama, moe, sparse_latent_moe,
                                   ssm_hybrid, ssm_moe, window_moe)

    MODELS.update({
        "tiny": llama.llama_tiny,
        "llama3-1b": llama.llama3_1b,
        "llama3-8b": llama.llama3_8b,
        "mixtral-tiny": moe.moe_tiny,
        "mixtral-8x7b": moe.mixtral_8x7b,
        "window-moe-tiny": window_moe.window_moe_tiny,
        "ssm-hybrid-tiny": ssm_hybrid.ssm_hybrid_tiny,
        "sparse-latent-moe-tiny": sparse_latent_moe.sparse_latent_moe_tiny,
        "mixed-latent-moe-tiny": sparse_latent_moe.mixed_latent_moe_tiny,
        "ssm-moe-tiny": ssm_moe.ssm_moe_tiny,
    })
    MOE_MODELS.update({"mixtral-tiny", "mixtral-8x7b"})
    EMBEDDING_MODELS.update({
        "bge-base": bert.bge_base,
        "bge-tiny": bert.bge_tiny,
    })


@sanitize.guard_class
class ServingCell(LifecycleMixin):
    def __init__(self, model: str, *, num_slots: int, max_seq_len: int | None,
                 checkpoint: str | None, dtype: str | None, seed: int = 0,
                 kv_cache_int8: bool | None = None,
                 decode_chunk: int | None = None,
                 kv_page_tokens: int | None = None,
                 max_pending: int | None = None,
                 deadline_s: float | None = None,
                 slo_ttft_p95_ms: float | None = None,
                 slo_availability: float | None = None,
                 role: str = "mixed",
                 chips: int | None = None):
        # Cold-start phase marks (monotonic). "boot_imports" is everything
        # between process start and constructor entry — interpreter boot,
        # module imports, argparse; the remaining phases are stamped as
        # the boot pipeline advances and exported by finish_boot().
        self._boot_marks: dict[str, float] = {"init_entry": time.monotonic()}
        import jax

        enable_compilation_cache()

        from kukeon_tpu.parallel import auto_mesh_shape, make_mesh, serving_mesh
        from kukeon_tpu.serving import ServingEngine

        _register_models()
        if model not in MODELS:
            raise SystemExit(
                f"unknown model {model!r}; known: "
                f"{sorted(MODELS) + sorted(EMBEDDING_MODELS)}"
            )
        import dataclasses

        # "int8" quantizes the weights post-load (activations stay bf16);
        # other dtype strings set the activation/weight dtype directly.
        quantize = dtype == "int8"
        cfg = MODELS[model]()
        if dtype and not quantize:
            import jax.numpy as jnp

            cfg = dataclasses.replace(cfg, dtype=getattr(jnp, dtype))
        if max_seq_len:
            cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)

        # Mesh: an explicit --chips N is the ModelSpec's grant — exactly N
        # chips, all on the tensor axis, dying loudly (serving_mesh) when
        # the grant exceeds what this process can see rather than silently
        # serving on fewer chips. Without the flag (bare/dev boots) the
        # cell keeps the old behavior: every visible device, factorized by
        # the auto heuristic.
        if chips is not None:
            try:
                mesh = serving_mesh(chips)
            except ValueError as e:
                raise SystemExit(f"--chips {chips}: {e}") from e
        else:
            n = len(jax.devices())
            shape = auto_mesh_shape(n)
            mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])

        # The family of the config says how to boot it, which forward the
        # engine jits and what a slot's cache holds (models/families.py); a
        # cell that asks a family for what it lacks ends here, loudly. An
        # unspecified lever the family lacks is pinned off, so a tuning
        # profile can never switch it on behind the guard.
        from kukeon_tpu.models import families

        family = families.of(cfg)
        families.refuse(family, model, {
            families.INT8_WEIGHTS: quantize,
            families.INT8_KV: bool(kv_cache_int8),
            families.PAGED: bool(kv_page_tokens),
            families.MESH: mesh.size > 1,
            families.CHECKPOINT: bool(checkpoint)})
        if families.INT8_KV not in family.supports:
            kv_cache_int8 = False
        if families.PAGED not in family.supports:
            kv_page_tokens = 0
        if checkpoint:
            params, cfg = family.load_checkpoint(
                checkpoint, cfg, quantize, max_seq_len)
        else:
            params = family.init_params(cfg, seed, quantize, mesh)

        self.model_name = model
        self.cfg = cfg
        # Disaggregated-serving role (mixed | prefill | decode). Policy,
        # not capability: every cell keeps the full engine — a prefill
        # cell can still decode locally (the gateway's fallback when no
        # decode replica is ready), a decode cell can still re-prefill a
        # preempted import. The role is advertised on /v1/stats so the
        # gateway's two-stage router builds its pools from the census.
        if role not in ("mixed", "prefill", "decode"):
            raise SystemExit(
                f"unknown --role {role!r}; must be mixed|prefill|decode")
        self.role = role
        # async_load: the multi-GB weight transfer streams in the background
        # while warmup()'s precompile pass AOT-compiles the programs — cold
        # start pays max(transfer, compile) instead of their sum.
        # model_name routes the engine to the persisted tune file
        # (serving/tuning.py): levers the operator left unset
        # (decode_chunk/kv_cache_int8 None) boot at the file's values for
        # this model+backend+chip-count.
        # One registry for the whole cell: engine metrics and cell
        # lifecycle gauges land in the same /metrics exposition.
        registry = Registry()
        self.engine = ServingEngine(
            cfg, params, mesh, num_slots=num_slots,
            max_seq_len=max_seq_len or min(cfg.max_seq_len, 4096),
            kv_cache_int8=kv_cache_int8, async_load=True,
            decode_chunk=decode_chunk, model_name=model,
            kv_page_tokens=kv_page_tokens,
            max_pending=max_pending, registry=registry,
        )
        from kukeon_tpu.serving.tokenizer import load_tokenizer

        self.tokenizer = load_tokenizer(checkpoint)
        self.started_at = time.time()
        self._stats_lock = sanitize.lock("ServingCell._stats_lock")
        self.total_tokens = 0   # guarded-by: _stats_lock
        # Default per-request deadline; a request's own deadlineS wins.
        self.default_deadline_s = deadline_s
        self._init_lifecycle()
        self._init_cell_obs(registry, kind="decoder")
        # SLO layer (obs/slo.py): burn rates + error-budget gauges computed
        # at scrape time from the engine's own requests/TTFT instruments.
        # Unset objectives fall back to the loose defaults so every cell
        # exposes the kukeon_slo_* families with a stable schema.
        d = SloObjectives()
        self.slo = SloTracker(registry, SloObjectives(
            availability=(slo_availability if slo_availability
                          else d.availability),
            ttft_p95_ms=(slo_ttft_p95_ms if slo_ttft_p95_ms
                         else d.ttft_p95_ms),
        ))
        self._boot_marks["init_exit"] = time.monotonic()

    @staticmethod
    def _load_checkpoint(path: str, cfg, quantize: bool = False):
        """(params-or-stream, cfg) from, in precedence order:

        - a kukeon int8 quantized checkpoint (kukeon_quant.json manifest) —
          the cold-start fast path: a tensor-granular CheckpointStream
          whose config and abstract shapes come from the manifest alone,
          so this returns before any tensor byte is read and the engine
          overlaps disk / cast / upload / compile;
        - an HF safetensors directory (config.json + *.safetensors, the hub
          layout) — the same streaming pipeline, host-quantizing per leaf
          when ``quantize`` (an 8B bf16 tree cannot be materialized on a
          16 GB chip);
        - an orbax checkpoint path (materialized — orbax has no
          tensor-granular reader here).
        """
        import os

        import jax

        from kukeon_tpu.models import checkpoints, llama

        if checkpoints.is_quantized_checkpoint(path):
            stream = checkpoints.stream_quantized(path, dtype=cfg.dtype)
            return stream, stream.cfg
        if os.path.isdir(path) and os.path.exists(os.path.join(path, "config.json")):
            from kukeon_tpu.models import hf_convert

            if quantize:
                stream = hf_convert.stream_params_quantized(
                    path, dtype=cfg.dtype)
            else:
                stream = hf_convert.stream_params(path, dtype=cfg.dtype)
            return stream, stream.cfg
        import orbax.checkpoint as ocp

        abstract = jax.eval_shape(lambda k: llama.init_params(k, cfg), jax.random.key(0))
        ckptr = ocp.StandardCheckpointer()
        params = ckptr.restore(path, abstract)
        if quantize:
            params = llama.quantize_params(params)
        return params, cfg

    def warmup(self, prompt_len: int = 64):
        # Compile first (needs shapes only — overlaps the async weight
        # transfer), then run the real warmup pass (needs the weights; the
        # "warmup" phase therefore also absorbs whatever remains of the
        # async checkpoint transfer).
        self.engine.precompile((prompt_len,))
        self._boot_marks.setdefault("compile_done", time.monotonic())
        try:
            self.engine.warmup(prompt_len)
        except RuntimeError as e:
            from kukeon_tpu.models.checkpoints import CheckpointStreamError

            if isinstance(e.__cause__, CheckpointStreamError):
                # A mid-stream read/decode failure (or the armed
                # checkpoint.stream fault point) must never leave a
                # half-loaded engine a step from /readyz. SystemExit is
                # NOT an Exception, so main()'s cache-bust retry does not
                # swallow it: the cell exits with a clear message and the
                # runner's restart policy recovers it on the same grant.
                raise SystemExit(
                    f"serving-cell: checkpoint stream failed during boot "
                    f"({e.__cause__}); exiting for the restart policy to "
                    f"recover") from e
            raise
        self._boot_marks.setdefault("warmup_done", time.monotonic())

    def finish_boot(self) -> dict[str, float]:
        """Close out the cold-start trace: compute the boot phase
        breakdown, export ``kukeon_cold_start_seconds`` (total) +
        ``kukeon_cold_start_phase_seconds{phase=}``, and drop a
        ``component="boot"`` span into the trace ring so ``kuke trace``
        can render the boot timeline like any request. Called once from
        main() right before the cell goes ready; chip_smoke.py reads these
        gauges off the first /metrics scrape."""
        now = time.monotonic()
        m = self._boot_marks
        phases: dict[str, float] = {
            "imports": m["init_entry"] - _PROC_T0,
            "init": m.get("init_exit", m["init_entry"]) - m["init_entry"],
        }
        if "compile_done" in m:
            phases["compile"] = m["compile_done"] - m.get("init_exit",
                                                          m["init_entry"])
            phases["warmup"] = m.get("warmup_done",
                                     m["compile_done"]) - m["compile_done"]
        total = now - _PROC_T0
        phases["serve"] = max(0.0, total - sum(phases.values()))
        # Streamed-checkpoint sub-phases (disk / cast / upload): measured
        # AFTER the serial partition above is closed, because they overlap
        # it — the reader threads' file reads and host casts and the load
        # thread's sharded uploads all run inside the init/compile/warmup
        # wall time. Their presence makes sum(phases) exceed the total;
        # that excess IS the overlap the streamed boot buys.
        eng = self.engine
        cs = (eng._ckpt_stream.stat_snapshot()
              if getattr(eng, "_ckpt_stream", None) is not None else {})
        load = {"disk": cs.get("disk_s", 0.0), "cast": cs.get("cast_s", 0.0),
                "upload": eng.load_stats.get("upload_s", 0.0)}
        if any(load.values()):
            phases.update(load)
        reg = self.registry
        reg.gauge(
            "kukeon_cold_start_seconds",
            "Process start -> ready wall time (the rolling-restart and "
            "autoscaling latency floor).").set(total)
        g = reg.gauge("kukeon_cold_start_phase_seconds",
                      "Cold-start breakdown by boot phase.",
                      labels=("phase",))
        for phase, dt in phases.items():
            g.set(dt, phase=phase)
        # Each event marks where its phase BEGINS, so the span's phase
        # durations (gap to the next event) mirror the gauge breakdown;
        # the tail gap (warmup start -> finished) covers warmup + serve.
        span = self.engine.tracer.begin(-2, 0, component="boot",
                                        start_mono=_PROC_T0)
        span.event("boot_imports", at=_PROC_T0)
        span.event("boot_init", at=m["init_entry"])
        if "compile_done" in m:
            span.event("boot_compile", at=m.get("init_exit",
                                                m["init_entry"]))
            span.event("boot_warmup", at=m["compile_done"])
        self.engine.tracer.finish(span, "ok")
        return phases

    def _parse_generate(self, req: dict):
        from kukeon_tpu.serving import SamplingParams

        if "promptTokens" in req:
            prompt = np.asarray(req["promptTokens"], np.int32)
        elif "prompt" in req:
            prompt = np.asarray(self.tokenizer.encode(req["prompt"]), np.int32)
        else:
            raise ValueError("need promptTokens or prompt")
        stops = req.get("stop", [])
        if isinstance(stops, str):
            stops = [stops]
        if not all(isinstance(s, str) and s for s in stops):
            raise ValueError("stop must be a non-empty string or list of them")
        sp = SamplingParams(
            temperature=float(req.get("temperature", 0.0)),
            top_k=int(req.get("topK", 0)),
            top_p=float(req.get("topP", 1.0)),
            max_new_tokens=int(req.get("maxNewTokens", 128)),
            stop_tokens=tuple(int(t) for t in req.get("stopTokens", [])),
        )
        prefix_id = req.get("prefixId")
        if prefix_id is not None and not isinstance(prefix_id, str):
            raise ValueError("prefixId must be a string")
        deadline_s = req.get("deadlineS", self.default_deadline_s)
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                raise ValueError("deadlineS must be positive")
        return prompt, sp, list(stops), prefix_id, deadline_s

    def generate(self, req: dict,
                 trace_ctx: "obs_trace.TraceContext | None" = None) -> dict:
        """Non-streaming generation: the terminal record of the streaming
        path (one machinery for both modes — stop handling included)."""
        out = None
        for out in self.generate_stream(req, trace_ctx=trace_ctx):
            pass
        if out.get("timedOut"):
            raise DeadlineExceeded(out["error"])
        if "error" in out:
            raise RuntimeError(out["error"])
        return {k: out[k] for k in ("tokens", "text", "numTokens", "seconds")}

    def generate_stream(self, req: dict,
                        trace_ctx: "obs_trace.TraceContext | None" = None):
        """Streaming generation: yields one JSON-line dict per token as the
        engine emits them (an agent session reads tokens as they decode
        instead of waiting for the full completion), then a terminal record
        with the aggregate fields.

        ``stop`` strings are matched against the accumulated decode; on a
        match the request is cancelled (the slot frees immediately) and the
        emitted text is cut at the match. ``stopTokens`` stop token-exactly
        inside the engine."""
        import queue as _q

        # Everything above the engine, on the profiler's clock: the body is
        # parsed, the request is not yet queued.
        with spans.span("cell.generate") as span:
            prompt, sp, stops, prefix_id, deadline_s = \
                self._parse_generate(req)
            events: _q.Queue = _q.Queue()
            t0 = time.monotonic()
            r = self.engine.submit(
                prompt, sp, emit=lambda tok, done: events.put((tok, done)),
                prefix_id=prefix_id, deadline_s=deadline_s,
                trace_ctx=trace_ctx)
            if r.trace is not None:
                span.set(request=r.trace.trace_id)
        yield from self._stream_events(r, events, stops, tokens=[],
                                       emitted="", t0=t0)

    def _stream_events(self, r, events, stops, *, tokens, emitted, t0,
                       skip_first=False):
        """The shared token-event loop behind generate_stream AND the KV
        handoff import: drain the engine's emit events, decode by prefix
        diff, match stop strings, then yield the terminal record.
        ``tokens``/``emitted`` may arrive pre-seeded (the import path
        already emitted the handed-off first token before seating);
        ``skip_first`` swallows the engine's re-emit of that token."""
        driving = not self.engine._running   # direct use without the thread
        stopped = False
        while True:
            if driving:
                while events.empty() and not r.done.is_set():
                    self.engine.step()
            tok, done = events.get()
            if skip_first:
                # The engine re-emits the imported first token at seat
                # time; its line already went out pre-seat (the handoff's
                # TTFT point), so only honor its terminal flag here.
                skip_first = False
                if not done:
                    continue
                tok = -1
            if tok >= 0 and not stopped:
                tokens.append(tok)
                # Incremental decode by prefix diff: decoding ids in
                # isolation breaks BPE merging (word-boundary markers,
                # multi-token UTF-8), so concatenated per-token text would
                # not equal the final decode.
                full = self.tokenizer.decode(tokens)
                hit = min((full.find(s) for s in stops if s in full),
                          default=-1)
                if hit >= 0:
                    full = full[:hit]
                    stopped = True
                    r.cancel()
                out = full
                if not (done or stopped):
                    # decode() is NOT append-only: a codepoint split across
                    # tokens decodes to U+FFFD now and is rewritten when the
                    # next token completes it. Hold back trailing U+FFFDs
                    # until they stabilize (the final event flushes them, so
                    # genuine replacement chars still arrive) — emitted text
                    # then never needs retracting.
                    out = full[:len(full) - _trailing_fffd(full)]
                if out.startswith(emitted):
                    delta = out[len(emitted):]
                else:
                    # Belt: a tokenizer that rewrites non-tail text (never
                    # the byte/BPE ones) — re-sync at the common prefix
                    # rather than slicing at a wrong offset.
                    n = min(len(out), len(emitted))
                    i = next((j for j in range(n) if out[j] != emitted[j]), n)
                    delta = out[i:]
                emitted = out
                if delta or not stopped:
                    yield {"token": tok, "text": delta}
            if done:
                break
        if r.timed_out:
            # In-band timeout terminal event: the deadline expiring mid-
            # stream must not masquerade as a transport error — partial
            # tokens are already on the wire, the terminal line names why
            # they stopped.
            yield {"error": f"deadline exceeded: {r.error}",
                   "timedOut": True, "numTokens": len(tokens)}
            return
        if r.error is not None:
            yield {"error": f"{type(r.error).__name__}: {r.error}"}
            return
        dt = time.monotonic() - t0
        with self._stats_lock:
            self.total_tokens += len(tokens)
        yield {
            "done": True,
            "tokens": tokens,
            "text": emitted if stops else self.tokenizer.decode(tokens),
            "numTokens": len(tokens),
            "seconds": round(dt, 4),
            "cancelled": bool(r.cancelled) and not stopped,
            "stopped": stopped,
        }

    # --- disaggregated serving: KV handoff -------------------------------

    def kv_export(self, req: dict,
                  trace_ctx: "obs_trace.TraceContext | None" = None) -> bytes:
        """Prefill-only handler behind ``POST /v1/kv/export``: run the
        prompt's prefill, fetch the KV block, and serialize it (plus the
        first sampled token and everything a decode cell needs to seat the
        request) in the handoff wire format. No decode slot is consumed on
        this cell — that is what makes a prefill pool's TTFT immune to
        decode occupancy."""
        import queue as _q

        prompt, sp, stops, prefix_id, deadline_s = self._parse_generate(req)
        events: _q.Queue = _q.Queue()
        r = self.engine.submit(prompt, sp,
                               emit=lambda tok, done: events.put((tok, done)),
                               prefix_id=prefix_id, deadline_s=deadline_s,
                               trace_ctx=trace_ctx, export=True)
        if not self.engine._running:    # direct use without the thread
            while not r.done.is_set():
                self.engine.step()
        r.done.wait()
        if r.timed_out:
            raise DeadlineExceeded(str(r.error))
        if r.error is not None:
            if isinstance(r.error, RejectedError):
                raise r.error
            raise RuntimeError(f"{type(r.error).__name__}: {r.error}")
        p = r.export_payload
        first = int(p["token"])
        first_text = self.tokenizer.decode([first])
        # A first token that is already terminal (eos, stop token, a
        # one-token budget, or a stop string it completes by itself) needs
        # no decode hop at all — the gateway answers from this header.
        hit = min((first_text.find(s) for s in stops if s in first_text),
                  default=-1)
        done = (hit >= 0
                or first in self.engine.eos_ids
                or first in sp.stop_tokens
                or sp.max_new_tokens <= 1)
        header = {
            "token": first,
            "text": first_text[:hit] if hit >= 0 else first_text,
            "length": int(p["length"]),
            "pageTokens": int(p["pageTokens"]),
            "model": self.model_name,
            "done": done,
            # Everything the decode cell needs to seat and continue the
            # request (tokenized HERE — the gateway has no tokenizer).
            "promptTokens": [int(t) for t in prompt],
            "maxNewTokens": sp.max_new_tokens,
            "temperature": sp.temperature,
            "topK": sp.top_k,
            "topP": sp.top_p,
            "stopTokens": list(sp.stop_tokens),
            "stop": stops,
            **({"prefixId": prefix_id} if prefix_id else {}),
            **({"deadlineS": deadline_s} if deadline_s else {}),
        }
        return pack_kv(header, p["k"], p["v"])

    def kv_import_stream(self, header: dict, k: np.ndarray, v: np.ndarray,
                         trace_ctx: "obs_trace.TraceContext | None" = None):
        """Seat a prefill cell's exported KV block into this cell's decode
        batch and stream the continuation (``POST /v1/kv/import``).

        The handed-off first token is emitted BEFORE the request waits for
        a decode slot — it already exists, so the client's TTFT is the
        prefill+transfer cost, not prefill plus decode-batch queueing;
        that ordering is the latency architecture of the handoff. The
        engine re-emits the token at seat time and the shared event loop
        swallows it (``skip_first``)."""
        import queue as _q

        faults.maybe_fail("kv.handoff")
        first = int(header["token"])
        n = int(header["length"])
        prompt = np.asarray(header.get("promptTokens", []), np.int32)
        stops = list(header.get("stop") or [])
        from kukeon_tpu.serving import SamplingParams

        sp = SamplingParams(
            temperature=float(header.get("temperature", 0.0)),
            top_k=int(header.get("topK", 0)),
            top_p=float(header.get("topP", 1.0)),
            max_new_tokens=int(header.get("maxNewTokens", 128)),
            stop_tokens=tuple(int(t) for t in header.get("stopTokens", [])),
        )
        deadline_s = header.get("deadlineS", self.default_deadline_s)
        t0 = time.monotonic()
        tokens = [first]
        full = self.tokenizer.decode(tokens)
        hit = min((full.find(s) for s in stops if s in full), default=-1)
        stopped = hit >= 0
        if stopped:
            full = full[:hit]
        done_now = (stopped or first in self.engine.eos_ids
                    or first in sp.stop_tokens or sp.max_new_tokens <= 1)
        emitted = (full if done_now
                   else full[:len(full) - _trailing_fffd(full)])
        if done_now:
            with self._stats_lock:
                self.total_tokens += 1
            yield {"token": first, "text": emitted}
            yield {"done": True, "tokens": tokens,
                   "text": emitted if stops else full,
                   "numTokens": 1, "seconds": round(
                       time.monotonic() - t0, 4),
                   "cancelled": False, "stopped": stopped}
            return
        # Submit BEFORE the first yield: a queue-full RejectedError must
        # surface before any body byte goes out, so the handler can still
        # answer a clean 429 the gateway's retry accounting understands.
        events: _q.Queue = _q.Queue()
        r = self.engine.submit(
            prompt, sp,
            emit=lambda tok, done: events.put((tok, done)),
            prefix_id=header.get("prefixId"), deadline_s=deadline_s,
            trace_ctx=trace_ctx,
            kv_import={"token": first, "length": n, "k": k, "v": v})
        # The handed-off first token goes out NOW, before the request has
        # a decode slot — TTFT is prefill+transfer, not seat-queue wait.
        yield {"token": first, "text": emitted}
        yield from self._stream_events(r, events, stops, tokens=tokens,
                                       emitted=emitted, t0=t0,
                                       skip_first=True)

    def kv_import(self, header: dict, k: np.ndarray, v: np.ndarray,
                  trace_ctx: "obs_trace.TraceContext | None" = None) -> dict:
        """Non-streaming import: drive the streaming path to its terminal
        record (one machinery for both modes, like generate)."""
        out = None
        for out in self.kv_import_stream(header, k, v, trace_ctx=trace_ctx):
            pass
        if out.get("timedOut"):
            raise DeadlineExceeded(out["error"])
        if "error" in out:
            raise RuntimeError(out["error"])
        return {key: out[key]
                for key in ("tokens", "text", "numTokens", "seconds")}

    def _idle(self) -> bool:
        # _requests is the engine's authoritative unfinished-request map —
        # it covers queued, slotted, AND mid-dispatch requests (queue depth
        # + free-slot counts have a window during prefill dispatch where
        # both read idle while a request is in flight).
        return super()._idle() and not self.engine._requests

    def _shutdown_engine(self):
        self.engine.stop()

    def stats(self) -> dict:
        """JSON stats view over the obs registry: every counter/gauge here
        reads the same instruments /metrics renders (shed_stats is a
        registry-counter view, the gauges are the registry's scrape-time
        callables) — one source of truth, two presentations."""
        reg = self.registry
        ready, unready_why = self.readiness()
        return {
            "model": self.model_name,
            # Disaggregation role census: the gateway's two-stage router
            # reads this off every poll to build its prefill/decode pools.
            "role": self.role,
            **_device_census(),
            "numSlots": int(reg.get("kukeon_engine_slots_total").value()),
            "freeSlots": int(reg.get("kukeon_engine_slots_free").value()),
            "uptimeSeconds": round(
                reg.get("kukeon_cell_uptime_seconds").value(), 1),
            "totalTokens": self.total_tokens,
            "generatedTokens": int(
                reg.get("kukeon_engine_tokens_total").value()),
            "prefixCache": {"hits": self.engine.prefix_hits,
                            "misses": self.engine.prefix_misses,
                            "entries": len(self.engine._prefix_cache)},
            "tuning": {
                "decodeChunk": self.engine.decode_chunk,
                "kvCacheInt8": self.engine.kv_cache_int8,
                "kvPageTokens": self.engine.page_tokens,
                "fromProfile": self.engine.tune is not None,
            },
            # Serving mesh: chip count and axis layout this engine's jitted
            # programs are sharded over (meshChips == 1 means single-chip).
            # getattr: harness fakes duck-type the engine without a mesh.
            "mesh": ({
                "chips": int(self.engine.mesh.size),
                "shape": {ax: int(sz) for ax, sz
                          in self.engine.mesh.shape.items() if sz > 1},
                "kvSharded": bool(
                    any(self.engine._cache_shardings()[0].spec)),
            } if getattr(self.engine, "mesh", None) is not None else None),
            # Paged KV pool occupancy (0/0 on the legacy layout): what the
            # operator watches to size kvPageTokens / the pool.
            "kvPages": {
                "total": self.engine.kv_pool_pages,
                "inUse": (self.engine._pool.in_use
                          if self.engine._pool is not None else 0),
                "preemptions": int(reg.get(
                    "kukeon_preemptions_total").value(reason="kv_pressure")),
                "shedKvExhausted": self.engine.shed_stats["kv_exhausted"],
            },
            # Overload/lifecycle counters (the shed accounting the stress
            # tier asserts on): queueDepth is live, rejected/timedOut are
            # monotonic totals since boot.
            "queueDepth": int(reg.get("kukeon_engine_queue_depth").value()),
            # Unfinished engine requests (queued + slotted + mid-dispatch):
            # the gateway's rollout polls this to see a drain go idle, and
            # it is the truthful "busy" signal (queueDepth alone reads 0
            # while slots are full).
            "inflight": len(self.engine._requests),
            "maxPending": self.engine.max_pending,
            "rejected": self.engine.shed_stats["rejected"],
            "timedOut": self.engine.shed_stats["timed_out"],
            "ready": ready,
            "draining": self.draining,
            **({"unreadyReason": unready_why} if unready_why else {}),
        }


@sanitize.guard_class
class EmbeddingCell(LifecycleMixin):
    """Embedding-model serving cell (bge-base): /v1/embed instead of
    /v1/generate; same health/stats seams as the decoder cell so the
    reconciler treats both cell flavors identically."""

    def __init__(self, model: str, *, batch_size: int = 16,
                 pooling: str = "cls", checkpoint: str | None = None,
                 dtype: str | None = None, seed: int = 0,
                 chips: int | None = None):
        import dataclasses

        import jax

        enable_compilation_cache()

        from kukeon_tpu.models import bert
        from kukeon_tpu.parallel import auto_mesh_shape, make_mesh, serving_mesh
        from kukeon_tpu.serving import EmbeddingEngine

        _register_models()
        cfg = EMBEDDING_MODELS[model]()
        if dtype:
            import jax.numpy as jnp

            cfg = dataclasses.replace(cfg, dtype=getattr(jnp, dtype))
        if chips is not None:
            try:
                mesh = serving_mesh(chips)
            except ValueError as e:
                raise SystemExit(f"--chips {chips}: {e}") from e
        else:
            n = len(jax.devices())
            shape = auto_mesh_shape(n)
            mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])
        if checkpoint:
            params = self._load_checkpoint(checkpoint, cfg)
        else:
            params = bert.init_params(jax.random.key(seed), cfg)

        self.model_name = model
        self.cfg = cfg
        self.engine = EmbeddingEngine(cfg, params, mesh,
                                      batch_size=batch_size, pooling=pooling)
        # The checkpoint's real tokenizer when it ships one (BASELINE config
        # 5 text inputs must not be byte-mangled for a real bge model);
        # byte fallback otherwise — same rule as the decoder cell.
        from kukeon_tpu.serving.tokenizer import load_tokenizer

        self.tokenizer = load_tokenizer(checkpoint)
        self.started_at = time.time()
        self._stats_lock = sanitize.lock("EmbeddingCell._stats_lock")
        self.total_sequences = 0   # guarded-by: _stats_lock
        self._init_lifecycle()
        self._init_cell_obs(Registry(), kind="embedding")
        self.registry.gauge(
            "kukeon_embed_batch_size",
            "Embedding micro-batch grid size.").set(batch_size)
        self.registry.register_collector(self._obs_collect)

    def _obs_collect(self):
        yield ("kukeon_embed_sequences_total", "counter",
               "Sequences embedded since boot.",
               [({}, float(self.total_sequences))])

    @staticmethod
    def _load_checkpoint(path: str, cfg):
        import jax
        import orbax.checkpoint as ocp

        from kukeon_tpu.models import bert

        abstract = jax.eval_shape(
            lambda k: bert.init_params(k, cfg), jax.random.key(0)
        )
        return ocp.StandardCheckpointer().restore(path, abstract)

    def warmup(self, prompt_len: int = 64):
        self.engine.warmup((prompt_len,))

    def embed(self, req: dict) -> dict:
        if "inputTokens" in req:
            prompts = [np.asarray(p, np.int32) for p in req["inputTokens"]]
        elif "inputs" in req:
            texts = req["inputs"]
            if isinstance(texts, str):
                texts = [texts]
            prompts = [np.asarray(self.tokenizer.encode(x) or [1], np.int32)
                       for x in texts]
        else:
            raise ValueError("need inputs or inputTokens")
        t0 = time.monotonic()
        vecs = self.engine.embed_batch(prompts)
        dt = time.monotonic() - t0
        with self._stats_lock:
            self.total_sequences += len(prompts)
        # One timeline record per embed batch: the embedding flavor's
        # "step" — same /v1/timeline schema spine as the decoder cell.
        self.recorder.record({
            "wall_s": round(dt, 6),
            "occupancy": len(prompts),
            "tokens": int(sum(p.size for p in prompts)),
            "programs": {"embed": round(dt, 6)},
            "traces": [],
        })
        return {
            "embeddings": [v.tolist() for v in vecs],
            "dim": int(vecs.shape[1]) if len(prompts) else self.cfg.hidden_size,
            "numSequences": len(prompts),
            "seconds": round(dt, 4),
        }

    def stats(self) -> dict:
        # ready/draining/uptime parity with the decoder cell's stats: a
        # scraper (or the reconciler) treats both cell flavors uniformly.
        ready, unready_why = self.readiness()
        return {
            "model": self.model_name,
            "kind": "embedding",
            **_device_census(),
            "batchSize": self.engine.batch_size,
            "uptimeSeconds": round(
                self.registry.get("kukeon_cell_uptime_seconds").value(), 1),
            "totalSequences": self.total_sequences,
            "ready": ready,
            "draining": self.draining,
            **({"unreadyReason": unready_why} if unready_why else {}),
        }


@sanitize.guard_class
class EngineWatchdog(threading.Thread):
    """Detects a wedged TPU runtime behind a stuck engine and gets the cell
    restarted instead of hanging forever.

    Failure mode: a wedged libtpu accepts work and then blocks a device
    call indefinitely — the engine driver thread is stuck inside jit
    dispatch, no Python-level timeout fires, and the cell sits Ready while
    serving nobody. The watchdog watches the engine's progress heartbeat;
    once work has been outstanding with no progress past ``stall_budget_s``
    it consults ``devices.probe_tpu_in_process`` — a bounded wait on a
    tiny transfer from a probe-owned thread of THIS process, because the
    cell holds the chip and no second process could open it. A "wedged"
    verdict trips the watchdog: ``on_wedged`` runs (the cell flips unready
    and exits WEDGED_EXIT_CODE) and the runner's restart policy + stable
    chip grant bring the cell back on its own chips. Any other verdict
    re-arms the budget — a long compile or a giant prefill is slow, not
    wedged, and must not get the cell killed.
    """

    def __init__(self, engine, *, stall_budget_s: float,
                 probe=None, on_wedged=None, interval_s: float | None = None,
                 probe_timeout_s: float = 20.0,
                 registry: Registry | None = None):
        super().__init__(daemon=True, name="tpu-watchdog")
        self.engine = engine
        self.stall_budget_s = stall_budget_s
        self.probe = probe
        self.on_wedged = on_wedged
        self.interval_s = interval_s if interval_s is not None else max(
            0.5, stall_budget_s / 4)
        self.probe_timeout_s = probe_timeout_s
        self.tripped = False
        self.last_verdict: tuple[str, str] | None = None
        self.probes = 0
        self._halt = sanitize.event("EngineWatchdog._halt")
        # Watchdog activity on the cell's scrape: every probe is a sign
        # the engine stalled past budget; a trip precedes the exit-86.
        reg = registry if registry is not None else Registry()
        self._m_probes = reg.counter(
            "kukeon_watchdog_probes_total",
            "TPU runtime probes fired after an engine stall.",
            labels=("verdict",))
        self._m_trips = reg.counter(
            "kukeon_watchdog_trips_total",
            "Wedged verdicts (the cell exits for restart right after).")

    def stop(self):
        self._halt.set()

    def run(self):
        probe = self.probe
        if probe is None:
            from kukeon_tpu.runtime.devices import probe_tpu_in_process
            probe = probe_tpu_in_process
        while not self._halt.wait(self.interval_s):
            if self.engine.stalled_s() < self.stall_budget_s:
                continue
            self.probes += 1
            status, detail = probe(timeout_s=self.probe_timeout_s)
            self.last_verdict = (status, detail)
            self._m_probes.inc(verdict=status)
            if status == "wedged":
                self.tripped = True
                self._m_trips.inc()
                if self.on_wedged is not None:
                    self.on_wedged(detail)
                return
            # Runtime answers: the stall is compute- or host-side. Treat the
            # probe completion as progress so the next probe waits a full
            # budget (no probe hammering during a legitimately long step).
            # Under the engine's admission lock: last_progress is
            # _lock-guarded state (kukesan surfaced this write as the
            # tree's one cross-thread unlocked heartbeat write).
            with self.engine._lock:
                self.engine.last_progress = time.monotonic()


def make_handler(cell: ServingCell):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):
            sys.stderr.write("serving-cell: " + fmt % a + "\n")

        def _send(self, code: int, obj: dict,
                  headers: dict[str, str] | None = None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_text(self, code: int, text: str, content_type: str):
            self._send_bytes(code, text.encode(), content_type)

        def _send_bytes(self, code: int, body: bytes, content_type: str):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _reject(self, e: RejectedError):
            """429 (engine queue full — retry against THIS cell) or 503
            (lifecycle: warming up/draining/wedged — retry elsewhere), both
            with Retry-After so clients back off instead of hammering."""
            import math

            ok, _why = (cell.readiness() if hasattr(cell, "readiness")
                        else (True, None))
            code = 429 if ok else 503
            self._send(code, {"error": str(e), "retryAfterSeconds":
                              e.retry_after_s},
                       headers={"Retry-After":
                                str(max(1, math.ceil(e.retry_after_s)))})

        def do_GET(self):
            from urllib.parse import parse_qs, urlsplit

            parts = urlsplit(self.path)
            path = parts.path
            if path == "/v1/health" or path == "/healthz":
                # Liveness: answering at all is the signal.
                self._send(200, {"status": "ok", "model": cell.model_name})
            elif path == "/readyz":
                ok, why = (cell.readiness() if hasattr(cell, "readiness")
                           else (True, None))
                if ok:
                    self._send(200, {"ready": True})
                else:
                    self._send(503, {"ready": False, "reason": why})
            elif path == "/v1/stats":
                self._send(200, cell.stats())
            elif path == "/metrics":
                # Prometheus text exposition over the cell's registry
                # (engine histograms + lifecycle gauges + fault counters).
                self._send_text(200, expo.render(cell.registry),
                                expo.CONTENT_TYPE)
            elif path == "/v1/trace":
                tracer = getattr(getattr(cell, "engine", None),
                                 "tracer", None)
                if tracer is None:
                    self._send(404, {"error": "this cell records no "
                                              "request traces"})
                    return
                q = parse_qs(parts.query)
                if "trace_id" in q:
                    # Distributed-trace pull: the daemon's Traces RPC (and
                    # `kuke trace <id>`) fan this out to every cell and
                    # union the spans into one timeline.
                    self._send(200, {"spans":
                                     tracer.for_trace(q["trace_id"][0])})
                    return
                if "request_id" in q:
                    # Exact-match pull: a slow request found in the logs is
                    # fetched directly instead of paging the ?n=K tail.
                    try:
                        rid = int(q["request_id"][0])
                    except ValueError:
                        self._send(400,
                                   {"error": "request_id must be an integer"})
                        return
                    self._send(200, {"spans": tracer.for_request(rid)})
                    return
                try:
                    n = int(q.get("n", ["50"])[0])
                except ValueError:
                    self._send(400, {"error": "n must be an integer"})
                    return
                self._send(200, {"spans": tracer.recent(n)})
            elif path == "/v1/profile":
                profiler = getattr(cell, "profiler", None)
                if profiler is None:
                    self._send(404, {"error": "this cell has no profiler"})
                    return
                self._send(200, {"captures": profiler.list(),
                                 "dir": profiler.base_dir,
                                 "keep": profiler.keep})
            elif path == "/v1/timeline":
                # The step flight recorder: last-N engine-loop step
                # records, oldest first. The daemon's Timeline RPC (and
                # `kuke timeline <cell>`) federate this across the fleet.
                recorder = getattr(cell, "recorder", None)
                if recorder is None:
                    self._send(404, {"error": "this cell records no "
                                              "step timeline"})
                    return
                q = parse_qs(parts.query)
                try:
                    n = int(q.get("n", ["50"])[0])
                except ValueError:
                    self._send(400, {"error": "n must be an integer"})
                    return
                self._send(200, {"steps": recorder.snapshot(n),
                                 "dropped": recorder.dropped,
                                 "capacity": recorder.capacity})
            else:
                self._send(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path == "/drain":
                started = (cell.begin_drain()
                           if hasattr(cell, "begin_drain") else False)
                self._send(200, {"draining": True, "started": started})
                return
            if self.path == "/v1/profile":
                # Start an on-demand device-profile capture. Deliberately
                # exempt from admission: profiling a draining or overloaded
                # cell is exactly when an operator wants a trace.
                profiler = getattr(cell, "profiler", None)
                if profiler is None:
                    self._send(404, {"error": "this cell has no profiler"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    unknown = sorted(set(req) - {"durationMs", "pythonTracer"})
                    if unknown:
                        # A field this route does not know must not start
                        # a capture the caller did not ask for.
                        raise ValueError(
                            f"unknown field(s) {unknown}: POST /v1/profile "
                            "takes durationMs and pythonTracer")
                    rec = profiler.start(
                        float(req.get("durationMs", 1000)),
                        python_tracer=bool(req.get("pythonTracer", False)))
                    self._send(200, {"started": True, "capture": rec})
                except ProfileBusy as e:
                    # Single-flight: one capture at a time (409 Conflict).
                    self._send(409, {"error": str(e)})
                except (ValueError, TypeError) as e:
                    self._send(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001 — server must keep serving
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            if self.path in ("/v1/kv/export", "/v1/kv/import"):
                self._kv_handoff()
                return
            routes = {}
            if hasattr(cell, "generate"):
                routes["/v1/generate"] = cell.generate
            if hasattr(cell, "embed"):
                routes["/v1/embed"] = cell.embed
            fn = routes.get(self.path)
            if fn is None:
                self._send(404, {"error": f"no route {self.path}; "
                                          f"this cell serves {sorted(routes)}"})
                return
            tracked = False
            try:
                faults.maybe_fail("cell.http")
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                # Distributed trace context: the gateway (or any client)
                # propagates a traceparent header; the engine's span joins
                # that trace instead of rooting a fresh one. Malformed
                # headers degrade to a fresh root trace.
                ctx = obs_trace.parse_traceparent(
                    self.headers.get(obs_trace.TRACEPARENT_HEADER))
                # Lifecycle admission first (503), then the engine's own
                # queue-full shedding fires inside submit (429).
                if hasattr(cell, "check_admission"):
                    cell.check_admission()
                if hasattr(cell, "_inflight_inc"):
                    cell._inflight_inc()
                    tracked = True
                if (self.path == "/v1/generate" and req.get("stream")
                        and hasattr(cell, "generate_stream")):
                    self._stream(cell.generate_stream(req, trace_ctx=ctx))
                    return
                if self.path == "/v1/generate" and hasattr(cell, "generate"):
                    self._send(200, cell.generate(req, trace_ctx=ctx))
                    return
                self._send(200, fn(req))
            except RejectedError as e:
                self._reject(e)
            except DeadlineExceeded as e:
                self._send(504, {"error": str(e), "timedOut": True})
            except ValueError as e:
                self._send(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — server must keep serving
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
            finally:
                if tracked:
                    cell._inflight_dec()

        def _kv_handoff(self):
            """The disaggregated-serving KV handoff surface:

            ``POST /v1/kv/export`` — JSON generate-shaped body in, binary
            KV block (header line + raw K/V rows) out; prefill only, no
            decode slot consumed.
            ``POST /v1/kv/import`` — binary KV block in, the continuation
            out (JSON, or ndjson when the header says ``stream``). Same
            admission/shed semantics as /v1/generate: lifecycle refusals
            are 503, engine queue pressure is 429 + Retry-After — the
            gateway's fallback logic keys off exactly those."""
            if not hasattr(cell, "kv_export"):
                self._send(404, {"error": "this cell serves no KV handoff"})
                return
            tracked = False
            try:
                faults.maybe_fail("cell.http")
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                ctx = obs_trace.parse_traceparent(
                    self.headers.get(obs_trace.TRACEPARENT_HEADER))
                cell.check_admission()
                cell._inflight_inc()
                tracked = True
                if self.path == "/v1/kv/export":
                    req = json.loads(body or b"{}")
                    self._send_bytes(200, cell.kv_export(req, trace_ctx=ctx),
                                     KV_CONTENT_TYPE)
                    return
                header, k, v = unpack_kv(body)
                if header.get("stream"):
                    self._stream(
                        cell.kv_import_stream(header, k, v, trace_ctx=ctx))
                    return
                self._send(200, cell.kv_import(header, k, v, trace_ctx=ctx))
            except RejectedError as e:
                self._reject(e)
            except DeadlineExceeded as e:
                self._send(504, {"error": str(e), "timedOut": True})
            except ValueError as e:
                self._send(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — server must keep serving
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
            finally:
                if tracked:
                    cell._inflight_dec()

        def _stream(self, gen):
            """Newline-delimited JSON, framed by connection close (the
            handler speaks HTTP/1.0). The first record is pulled before
            headers go out so parse errors still surface as a clean 400."""
            import itertools

            try:
                first = next(gen)
            except RejectedError as e:
                # The engine sheds inside submit(), which runs lazily at the
                # first pull — headers are not out yet, so the rejection can
                # still travel as a clean 429/503.
                self._reject(e)
                return
            except ValueError as e:
                self._send(400, {"error": str(e)})
                return
            except StopIteration:
                self._send(500, {"error": "empty stream"})
                return
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.end_headers()
            try:
                for obj in itertools.chain([first], gen):
                    self.wfile.write((json.dumps(obj) + "\n").encode())
                    self.wfile.flush()
            except OSError:
                pass   # client went away mid-stream; nothing to tell it
            except Exception as e:  # noqa: BLE001 — headers are already out
                # A second status line (do_POST's 500 path) would land
                # inside the open ndjson body and corrupt the stream; the
                # in-band terminal error line is the protocol here.
                try:
                    self.wfile.write(
                        (json.dumps({"error": f"{type(e).__name__}: {e}"})
                         + "\n").encode())
                    self.wfile.flush()
                except OSError:
                    pass

    return Handler


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kukeon-serving-cell")
    ap.add_argument("--model", required=True)
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--dtype", default=None)
    # None (flag absent) lets the persisted tune file decide; the
    # explicit flag always wins (serving/tuning.py).
    ap.add_argument("--kv-cache-int8", action="store_true", default=None)
    ap.add_argument("--decode-chunk", type=int, default=None)
    # Paged KV cache (ModelSpec kvPageTokens): > 0 = page size in KV rows,
    # 0 = pin the legacy contiguous layout, absent = profile decides.
    ap.add_argument("--kv-page-tokens", type=int, default=None)
    # Disaggregated serving role (ModelSpec role): what the gateway's
    # two-stage router reads off /v1/stats. Policy, not capability — every
    # role keeps the full engine.
    ap.add_argument("--role", choices=("mixed", "prefill", "decode"),
                    default="mixed")
    # Serving mesh size (ModelSpec chips): exactly N visible chips, all on
    # the tensor axis. Absent = every visible device, auto-factorized —
    # the pre-multi-chip behavior.
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--no-warmup", action="store_true")
    # Admission control: bound the pending queue (shed with 429 past it)
    # and default every request to a deadline (expired requests free their
    # slot and answer in-band). 0 disables either.
    ap.add_argument("--max-pending", type=int, default=64)
    ap.add_argument("--deadline-s", type=float, default=0.0)
    # SLO objectives (ModelSpec sloTtftP95Ms / sloAvailability): drive the
    # kukeon_slo_* burn-rate gauges on /metrics. 0 = use the loose default.
    ap.add_argument("--slo-ttft-p95-ms", type=float, default=0.0)
    ap.add_argument("--slo-availability", type=float, default=0.0)
    args = ap.parse_args(argv)

    _register_models()

    def build():
        if args.model in EMBEDDING_MODELS:
            cell = EmbeddingCell(args.model, batch_size=args.num_slots,
                                 checkpoint=args.checkpoint, dtype=args.dtype,
                                 chips=args.chips)
            if not args.no_warmup:
                cell.warmup()
            return cell
        cell = ServingCell(
            args.model, num_slots=args.num_slots, max_seq_len=args.max_seq_len,
            checkpoint=args.checkpoint, dtype=args.dtype,
            kv_cache_int8=args.kv_cache_int8, decode_chunk=args.decode_chunk,
            kv_page_tokens=args.kv_page_tokens,
            max_pending=args.max_pending or None,
            deadline_s=args.deadline_s or None,
            slo_ttft_p95_ms=args.slo_ttft_p95_ms or None,
            slo_availability=args.slo_availability or None,
            role=args.role, chips=args.chips,
        )
        # Warmup before the engine thread starts: step() is single-driver.
        if not args.no_warmup:
            cell.warmup()
        cell.engine.start()
        return cell

    try:
        cell = build()
    except Exception as e:  # noqa: BLE001 — one self-heal attempt
        # A poisoned persistent-cache entry (stale AOT vs rolled libtpu,
        # truncated write) would otherwise crash-loop the cell forever under
        # restartPolicy: always. Bust the cache and recompile once; rethrow
        # if the failure had nothing to do with the cache.
        if not _bust_compilation_cache():
            raise
        print(f"serving-cell: init failed ({type(e).__name__}: {e}); "
              "busted persistent compilation cache, retrying once",
              file=sys.stderr, flush=True)
        cell = build()
    server = ThreadingHTTPServer((args.host, args.port), make_handler(cell))
    # /readyz goes true only now: weights loaded, warmup done, server bound.
    cell.on_drained = server.shutdown
    if isinstance(cell, ServingCell):
        # Close out the cold-start trace: kukeon_cold_start_seconds (+ the
        # per-phase breakdown) lands on /metrics and the boot span joins
        # the trace ring — chip_smoke.py reads the gauges.
        cell.finish_boot()
    cell.mark_ready()

    # SIGTERM = drain (the runner's stop path sends it with a grace window):
    # stop admitting, finish in-flight, exit 0. A second SIGTERM (or the
    # runner's SIGKILL after the grace) still kills immediately.
    import signal as _signal

    _signal.signal(_signal.SIGTERM, lambda *_a: cell.begin_drain())

    # TPU watchdog: a stuck engine step past the stall budget, confirmed
    # wedged by the runtime probe, exits WEDGED_EXIT_CODE so the restart
    # policy recovers the cell (same chip grant, runner._chip_slices).
    watchdog = None
    budget = float(os.environ.get(WATCHDOG_ENV, "120") or 0)
    if budget > 0 and isinstance(cell, ServingCell):

        def _wedged(detail: str):
            cell.mark_unready(f"TPU runtime wedged: {detail}")
            print(f"serving-cell: watchdog tripped — {detail}; exiting "
                  f"{WEDGED_EXIT_CODE} for restart", file=sys.stderr,
                  flush=True)
            os._exit(WEDGED_EXIT_CODE)

        watchdog = EngineWatchdog(
            cell.engine, stall_budget_s=budget, on_wedged=_wedged,
            probe_timeout_s=float(
                os.environ.get(WATCHDOG_PROBE_TIMEOUT_ENV, "20") or 20),
            registry=cell.registry,
        )
        watchdog.start()

    print(f"serving-cell: {args.model} ready on {args.host}:{args.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if watchdog is not None:
            watchdog.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
