"""Continuous-batching serving engine (the JetStream-style model-cell core).

The reference runtime (eminwux/kukeon) has no model math; the TPU build's
north star adds an in-tree JAX serving cell (BASELINE.json: Llama-3-8B agent
serving at >=1500 aggregate tok/s on v5e-8). This module is that serving
core, designed for TPU:

- **Slot-based decode batch**: a fixed [B_slots] decode batch with a
  fixed-shape KV cache [L, B, S_max, KV, D]. Static shapes => one compiled
  decode program; occupancy changes never recompile. The state HOLDS k/v as
  [L, B, KV, S_max, D] (``_kv_major``): the layout the TPU compiler gives the
  decode scan's carry, so no chunk transposes the cache into the scan and out.
- **Paged KV cache (``kv_page_tokens > 0``)**: instead of reserving
  ``num_slots * S_max`` contiguous rows, HBM is owned as fixed-size pages
  ([L, P, page_tokens, KV, D], serving/kv_pages.py) with a per-slot block
  table threaded into the jitted programs — gather/scatter by page index
  replaces slot-contiguous cache views. Pages alloc/free page-granularly as
  requests are admitted, grow, and finish, so mixed-length agent traffic
  packs the chip instead of fragmenting it; under memory pressure the
  lowest-priority in-flight request is *preempted* (pages reclaimed,
  request requeued ahead of new admissions, re-prefilled on resume), and
  prefix-cache entries become shared read-only pages with refcounts — N
  sessions on one agent prefix pay its KV cost once. The block table is a
  [B, S_max/page_tokens] int32 array with static shape, so the decode
  program still never recompiles across occupancy churn, and it is
  device-cached with a dirty flag like the sampling arrays, so steady-state
  chunks still perform exactly one blocking transfer (the token fetch).
- **Disaggregated prefill/insert/decode programs**: prefill runs per request
  at a small set of bucketed lengths (bounded compile cache), its KV block is
  inserted into a free slot, and the decode program generates tokens for
  every active slot.
- **Chunked multi-step decode**: decode runs K steps in one ``lax.scan`` on
  device, sampling included, and transfers a single [B, K] token block back.
  One dispatch per K tokens instead of per token — this amortizes the
  per-dispatch host cost and removes Python from the inner loop entirely.
- **Double-buffered dispatch**: chunk N+1 is dispatched *before* chunk N's
  token block is fetched, so the blocking readback overlaps the next
  chunk's compute instead of serializing with it. Tokens therefore emit one chunk behind the device; a request
  finishing mid-flight overshoots at most one extra chunk, whose tokens are
  discarded (same overshoot contract the scheduler already has).
- **Donation**: decode state (cache) is donated, so the multi-GB cache is
  updated in place in HBM.
- **Sharding**: params tensor-sharded over the mesh; cache sharded on
  kv-heads over ``tensor``; decode batch replicated (latency path) — XLA
  inserts the psums over ICI.

Python's role is only orchestration: queueing requests, picking slots,
copying sampled token blocks out. All math is inside three jitted programs.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from collections.abc import Mapping
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kukeon_tpu import faults, sanitize
from kukeon_tpu.models import families, kv_kinds, llama
from kukeon_tpu.ops.attention import decode_block_rows
from kukeon_tpu.serving.kv_pages import (
    SCRATCH_PAGE,
    PageAllocator,
    PagePoolExhausted,
    SharedPrefix,
)
from kukeon_tpu.obs import (
    CompileTracker,
    FlightRecorder,
    ProgramTimers,
    Registry,
    Tracer,
    device_memory_collector,
    faults_collector,
    op_impl_collector,
)
from kukeon_tpu.obs.spans import LoopSpans
from kukeon_tpu.parallel import sharding as shd
from kukeon_tpu.serving.sampling import (
    SamplingParams,
    sample_per_slot,
    slot_sampling_arrays,
)

PREFILL_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)

_LOG = logging.getLogger("kukeon.serving.engine")


def _kv_major(x: jnp.ndarray) -> jnp.ndarray:
    """[L, B, S, KV, D] <-> [L, B, KV, S, D]; its own inverse.

    The contiguous decode state holds k/v with KV heads major to rows, which
    is the layout the TPU compiler gives the decode scan's carry: insert
    writes a prefill's block swapped, decode_chunk swaps the axes on its way
    into the scan and back out, and the compiler makes those two swaps
    relabellings of the same bytes. A state held row-major makes every chunk
    transpose K and V into the scan and back out of it: four whole-cache
    copies a chunk whatever its length, and a cache and more of temporaries."""
    return jnp.swapaxes(x, 2, 3)


class _CounterMapView(Mapping):
    """Read-only dict view over a labelled registry counter.

    PR 2's ``shed_stats`` dict migrated onto the metrics registry; this
    keeps every existing reader (``/v1/stats``, tests, operators poking the
    engine in a REPL) working unchanged while the registry is the single
    source of truth the Prometheus exposition scrapes."""

    def __init__(self, counter, label: str, keys: tuple[str, ...]):
        self._counter = counter
        self._label = label
        self._keys = keys

    def __getitem__(self, key: str) -> int:
        if key not in self._keys:
            raise KeyError(key)
        return int(self._counter.value(**{self._label: key}))

    def __iter__(self):
        return iter(self._keys)

    def __len__(self) -> int:
        return len(self._keys)


class RejectedError(RuntimeError):
    """Request shed by admission control (queue full, draining, or unready).

    Carries ``retry_after_s`` so HTTP front-ends can answer 429/503 with a
    concrete ``Retry-After`` instead of inviting an immediate retry storm.
    """

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class DeadlineExceeded(RuntimeError):
    """A request's deadline passed before it finished generating."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Whole-engine decode state; lives sharded in HBM between steps."""

    # Contiguous: k/v HELD [L, B, KV, S_max, D] (_kv_major: the decode scan's
    # own layout), scales [L, B, S_max, KV]; paged: the pool [L, P, pt, KV, D].
    # A family whose layers are of several kinds: kv_kinds.LayeredKV, what
    # each kind holds by name (a ring of a window's rows beside full stacks;
    # a state-space layer's state, which has no rows).
    cache: "llama.KVCache | kv_kinds.LayeredKV"     # + lengths [B]
    tokens: jnp.ndarray           # [B] int32 — last emitted token per slot
    active: jnp.ndarray           # [B] bool — slot currently generating


@dataclasses.dataclass
class Request:
    """One generation request, as tracked by the engine."""

    id: int
    prompt: np.ndarray
    sampling: SamplingParams
    # (token, done); a cancelled request's terminal event is (-1, True).
    emit: Callable[[int, bool], None] | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    error: Exception | None = None
    slot: int = -1
    submitted_at: float = 0.0
    first_token_at: float = 0.0
    last_token_at: float = 0.0
    # Observability: the request's trace span (obs/trace.py). The engine
    # driver stamps lifecycle events on it; /v1/trace exports it.
    trace: Any = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    cancelled: bool = False
    # Absolute monotonic deadline (None = no deadline). Checked at dequeue
    # and once per driver iteration (i.e. per decode chunk): an expired
    # request emits the in-band timeout terminal event and frees its slot.
    deadline: float | None = None
    timed_out: bool = False
    # Prefix-cache participation (agent sessions share a system prompt /
    # growing conversation): requests with the same prefix_id reuse the
    # stored prompt KV and prefill only the new suffix.
    prefix_id: str | None = None
    # Paged-KV preemption (kv_pages): a preempted request lost its slot and
    # pages under memory pressure; it sits in the resume queue (ahead of new
    # admissions) and re-prefills prompt+generated when re-admitted.
    # ``requeued`` also marks that the request already left the _pending_n
    # admission count — terminal paths must not decrement it again.
    preemptions: int = 0
    requeued: bool = False
    # Disaggregated serving (KV handoff). ``export=True`` runs prefill ONLY:
    # no slot is seated, no pages are allocated — the dense prefill KV block
    # is fetched to host (through the counted ``_fetch`` seam) and handed
    # back on ``export_payload`` with the first sampled token; a decode cell
    # imports it and continues generation without re-running prefill.
    export: bool = False
    export_payload: "dict | None" = None
    # Import side: {"token", "length", "k", "v"} — host numpy KV rows
    # [L, 1, length, KV, D] from a prefill cell's export. The request seats
    # directly into a decode slot (``insert_paged``/``insert`` scatter the
    # block home); if it is later preempted, ``generated`` is non-empty and
    # the resume path re-prefills locally like any preempted request.
    kv_import: "dict | None" = None

    def cancel(self) -> None:
        """Ask the engine to stop generating for this request. Thread-safe:
        only sets a flag; the driver (step loop) acts on it on its next
        iteration — releasing the slot for an active request, or completing
        a still-queued one without waiting for a slot — so engine state is
        never touched off-thread. Waiters wake via ``done``."""
        self.cancelled = True


@dataclasses.dataclass
class _CachedPrefix:
    """Stored prompt KV for one prefix_id (device arrays)."""

    tokens: np.ndarray               # the exact prompt this KV encodes (int32)
    kv_k: Any                        # [L, 1, Pb, KV, D], Pb a CANONICAL bucket
    kv_v: Any
    length: int                      # valid positions in the block

    @property
    def nbytes(self) -> int:
        return int(self.kv_k.nbytes + self.kv_v.nbytes)


@dataclasses.dataclass
class _InflightChunk:
    """A dispatched-but-unfetched decode chunk (double buffering)."""

    tokens: Any                              # device array [B, K]
    k: int
    slots: list[tuple[int, "Request"]]       # (slot, request) at dispatch time


def _request_tag(req: "Request") -> str:
    """A request's identifier on its spans: its trace id, the one /v1/trace
    and /v1/timeline use; the engine's own id where it carries no trace."""
    return req.trace.trace_id if req.trace is not None else str(req.id)


def bucket_length(n: int, buckets: tuple[int, ...] = PREFILL_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    # Beyond the largest bucket: its next doubling (8192, 16384, 32768 ...
    # past 4096), so that a long context costs a program an octave and a
    # warm-up that names the doublings has compiled every one of them.
    last = buckets[-1]
    while last < n:
        last *= 2
    return last


@sanitize.guard_class
class ServingEngine:
    """Slot-based continuous-batching engine over a jitted Llama.

    Thread model: callers enqueue via :meth:`submit`; a single engine thread
    (or the caller via :meth:`step`) drives prefill+decode. One engine owns
    its params/cache; run one engine per model cell. ``_lock`` guards the
    admission state (``_pending_n``/``_next_id``/``_requests``/
    ``last_progress``/``_running``) and doubles as the ``_work`` condition's
    lock — the engine loop sleeps on ``_work`` when idle and submit/stop
    notify it. Under ``KUKEON_SANITIZE=1`` the lock is a kukesan recording
    proxy (hot: blocking calls while holding it are findings) and this
    class's guarded-by contract is enforced on every attribute write.
    """

    def __init__(
        self,
        cfg: llama.LlamaConfig,
        params: Any,
        mesh: Mesh,
        *,
        num_slots: int = 8,
        max_seq_len: int | None = None,
        eos_ids: tuple[int, ...] = (),
        decode_chunk: int | None = None,
        seed: int = 0,
        kv_cache_int8: bool | None = None,
        async_load: bool = False,
        forward_fn=None,
        param_specs=None,
        prefix_cache_size: int = 8,
        prefix_cache_bytes: int = 2 << 30,
        prefill_buckets: tuple[int, ...] | None = None,
        model_name: str | None = None,
        max_pending: int | None = None,
        registry: Registry | None = None,
        kv_page_tokens: int | None = None,
        kv_pool_pages: int | None = None,
        kv_shard: bool | None = None,
    ):
        # Model pluggability (models/families.py): a family is its config's
        # type. Most serve through one forward with llama.forward's signature
        # ((params, cfg, tokens, positions, cache) -> (logits, cache')) and
        # the shared KVCache layout — models/moe.py is the second; one whose
        # layers hold different kinds of state brings ``layered`` instead.
        # ``forward_fn`` / ``param_specs`` override the record's (a caller
        # that builds the engine from shapes passes them itself).
        self.family = families.find(cfg)
        self._layered = self.family.layered if self.family else None
        self._forward = forward_fn or (
            self.family.forward if self.family else None) or llama.forward
        self._param_specs = param_specs
        # Streamed checkpoint boot (models/checkpoints.CheckpointStream,
        # duck-typed on .abstract_params): the constructor sees only the
        # manifest-derived abstract tree — shardings and _abstract_params
        # come from shapes alone, so precompile() can start before any
        # tensor byte is read — while the async_load thread drains the
        # stream leaf-by-leaf through the counted _upload seam.
        self._ckpt_stream = (params if hasattr(params, "abstract_params")
                             else None)
        ptree = (self._ckpt_stream.abstract_params
                 if self._ckpt_stream is not None else params)
        if (self._param_specs is None and self.family is not None
                and self.family.param_specs is not None):
            self._param_specs = self.family.param_specs(ptree)
        # Forwards that accept ``logit_positions`` let prefill compute the
        # LM head at ONE position instead of all S bucket rows — at 8B
        # shapes that removes a [S, 128k] f32 logits tensor (and its S×H×V
        # matmul) from every prefill, work that otherwise stalls decode.
        # Those that accept ``active`` are told which slots a decode step
        # serves, so that the others read no cache row.
        import inspect

        try:
            accepts = inspect.signature(self._forward).parameters
        except (TypeError, ValueError):
            accepts = ()
        self._fwd_logit_positions = "logit_positions" in accepts
        self._fwd_active = "active" in accepts

        # Tuning profile: levers not pinned by the caller fall back to the
        # persisted tune for this (model, backend, chip-count), then to
        # defaults. An operator writes the file by hand (README "Tuning
        # serving throughput"); a stale or missing one silently degrades to
        # defaults (serving/tuning.py).
        self.tune: "Any | None" = None
        if model_name and (decode_chunk is None or kv_cache_int8 is None
                           or prefill_buckets is None
                           or kv_page_tokens is None or kv_shard is None):
            from kukeon_tpu.serving import tuning

            self.tune = tuning.load(
                model_name, jax.default_backend(),
                mesh.size if mesh is not None else 0,
            )
        if self.tune is not None:
            if decode_chunk is None:
                decode_chunk = self.tune.decode_chunk
            if kv_cache_int8 is None:
                kv_cache_int8 = self.tune.kv_cache_int8
            if prefill_buckets is None:
                prefill_buckets = self.tune.prefill_buckets
            # kv_page_tokens: None = let the profile decide, 0 = force the
            # legacy contiguous layout, > 0 = paged with that page size.
            if kv_page_tokens is None:
                kv_page_tokens = self.tune.kv_page_tokens
            # kv_shard: None = profile (then the divisibility default),
            # False = replicate the KV cache even on a sharded mesh.
            if kv_shard is None:
                kv_shard = self.tune.kv_shard
        decode_chunk = 16 if decode_chunk is None else decode_chunk
        kv_cache_int8 = bool(kv_cache_int8)
        self.model_name = model_name
        self.prefill_buckets = (
            tuple(sorted({int(b) for b in prefill_buckets}))
            if prefill_buckets else PREFILL_BUCKETS
        )
        self.cfg = cfg
        self.mesh = mesh
        # KV-shard lever: None = shard over the mesh's
        # tensor axis when the KV-head count divides it, False = replicate
        # the cache (more HBM, no gather in the attention dots), True =
        # shard — still subject to the divisibility fallback below.
        self.kv_shard = kv_shard
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.eos_ids = set(eos_ids)
        self.decode_chunk = max(1, decode_chunk)
        # Paged KV cache (serving/kv_pages.py): pages of ``kv_page_tokens``
        # rows replace the slot-contiguous [B, S_max] reservation. Shapes
        # stay static — the decode view is always [B, S_max] — but only the
        # pages a request actually uses are allocated, so the pool can be
        # sized well below num_slots * S_max and preemption absorbs the
        # overflow. page size must tile max_seq_len and every usable prefill
        # bucket, or insert-time scatters would split a page across slots.
        self.page_tokens = int(kv_page_tokens or 0)
        self.paged = self.page_tokens > 0
        self._pool: PageAllocator | None = None
        # A layered family's kinds of per-layer state, as data; the dense
        # path's one kind (every layer, S_max rows) only labels the gauge.
        self._kinds: tuple[kv_kinds.CacheKind, ...] = (
            self._layered.kinds(cfg, self.max_seq_len) if self._layered
            else (kv_kinds.CacheKind("full", tuple(range(cfg.num_layers)),
                                     self.max_seq_len),))
        # Of each kind, the rows of the blocks the decode attention reads in,
        # or None where it reads every row: what
        # kukeon_engine_decode_kv_rows_total counts from.
        self._kv_blocks = tuple(
            decode_block_rows(
                cfg.num_heads, cfg.num_kv_heads, kd.rows, cfg.head_dim,
                cfg.dtype, jnp.int8 if kv_cache_int8 else cfg.dtype,
                mesh.size if mesh is not None else 1)
            if kd.rows and not kd.arrays else None
            for kd in self._kinds)
        # The kinds whose slots hold state without rows: what
        # kukeon_engine_state_slot_steps_total counts from.
        self._state_kinds = sum(1 for kd in self._kinds if kd.state)
        if self._layered and (self.paged or kv_cache_int8
                              or (mesh is not None and mesh.size > 1)):
            raise ValueError(
                f"family {self.family.name!r} serves from a contiguous bf16 "
                "cache on one chip: no paged KV, int8 KV or sharded mesh yet")
        if self.paged:
            pt = self.page_tokens
            if self.max_seq_len % pt:
                raise ValueError(
                    f"kv_page_tokens {pt} must divide max_seq_len "
                    f"{self.max_seq_len}")
            bad = [b for b in self.prefill_buckets
                   if b < self.max_seq_len and b % pt]
            if bad:
                raise ValueError(
                    f"kv_page_tokens {pt} must divide every prefill bucket "
                    f"below max_seq_len; offending buckets: {bad}")
            self.max_pages_per_slot = self.max_seq_len // pt
            self.kv_pool_pages = int(
                kv_pool_pages or num_slots * self.max_pages_per_slot)
            self._pool = PageAllocator(self.kv_pool_pages, pt)
            # Per-page HBM bytes (K + V + scales): what a prefix entry pins
            # against the prefix-cache byte budget in paged mode.
            row = cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
            itemsize = 1 if kv_cache_int8 else np.dtype(cfg.dtype).itemsize
            self._page_bytes = 2 * pt * row * itemsize
            if kv_cache_int8:
                self._page_bytes += (
                    2 * pt * cfg.num_layers * cfg.num_kv_heads * 4)
        else:
            self.max_pages_per_slot = 0
            self.kv_pool_pages = 0
        # int8 KV cache: halves the cache's HBM bytes per decode step (the
        # stream that grows with context length and slot count); dequant is
        # fused into the decode attention dots. Prefill stays full-precision;
        # quantization happens once, at slot insert.
        self.kv_cache_int8 = kv_cache_int8
        self._key = jax.random.key(seed)
        # Transfer-counting seam (the decode roofline contract): every
        # blocking device→host readback goes through _fetch and every
        # host→device array upload through _upload, so tests can assert the
        # decode loop performs ≤1 blocking transfer per chunk instead of
        # guessing from timings. "chunks" counts dispatched decode chunks.
        # *_s accumulate wall time spent blocked in each transfer kind
        # (scraped as kukeon_engine_host_sync_seconds_total).
        self.sync_stats = {"fetches": 0, "uploads": 0, "chunks": 0,
                           "fetch_s": 0.0, "upload_s": 0.0}
        # Streamed-boot upload accounting, separate from sync_stats so the
        # serving-path host-sync budget and the one-off checkpoint transfer
        # never share a ledger (kukeon_checkpoint_load_seconds{stage=upload}
        # reads this; the cell's boot breakdown sums it with the stream's
        # own disk/cast numbers).
        self.load_stats = {"upload_s": 0.0, "bytes": 0, "tensors": 0}

        if mesh is None:
            raise ValueError("ServingEngine requires a mesh (use make_mesh(tensor=1) for one device)")
        # Abstract (shape+sharding) view of the params, available before any
        # byte reaches the device — what precompile() lowers against.
        self._shardings = shd.param_shardings(ptree, mesh, specs=self._param_specs)
        self._abstract_params = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            ptree, self._shardings,
        )
        self._load_exc: Exception | None = None
        self._loaded = sanitize.event("ServingEngine._loaded")
        if async_load:
            # Weight transfer off-thread so cold start can overlap it with
            # precompile(): the boot pays max(transfer, compile), not the
            # sum. With a CheckpointStream the same thread consumes device-ready
            # leaves AS THEY ARRIVE off disk, collapsing the whole boot to
            # max(disk, transfer, compile).
            self.params = None

            def _load():
                try:
                    if self._ckpt_stream is not None:
                        self.params = self._consume_stream(self._ckpt_stream)
                    else:
                        self.params = shd.shard_params(
                            params, mesh, specs=self._param_specs)
                    with jax.set_mesh(mesh):
                        self.state = self._init_state()
                except Exception as e:  # noqa: BLE001 — surfaced by _ensure_loaded
                    self._load_exc = e
                finally:
                    self._loaded.set()

            threading.Thread(target=_load, daemon=True,
                             name="engine-weight-load").start()
        else:
            if self._ckpt_stream is not None:
                self.params = self._consume_stream(self._ckpt_stream)
            else:
                self.params = shd.shard_params(params, mesh,
                                               specs=self._param_specs)
            with jax.set_mesh(mesh):
                self.state = self._init_state()
            self._loaded.set()

        self._requests: dict[int, Request] = {}
        self._slot_req: list[Request | None] = [None] * num_slots
        self._slot_len: list[int] = [0] * num_slots    # host-side cache lengths
        self._inflight: _InflightChunk | None = None
        # Slots that were seated when this step's admit began (each holds a
        # first token): the `decoding` of its engine.prefill_dispatch spans.
        self._decoding = 0
        # Device-resident sampling arrays, re-uploaded only when the slot
        # composition changes (each host->device upload costs a link RT).
        # The dirty flag is set exactly where composition changes (slot
        # insert/release, failure sweep) so steady-state chunks touch no
        # host memory at all — not even a numpy rebuild-and-compare.
        self._sampling_dev: tuple | None = None
        self._sampling_dirty = True
        # Paged block tables: host truth is a [B, max_pages] int32 array
        # (released slots zeroed -> their in-flight writes land in scratch);
        # the device copy re-uploads only when a slot's page list changed —
        # same dirty-flag discipline as the sampling arrays, so steady-state
        # decode chunks still touch no host memory.
        self._bt = (np.zeros((num_slots, self.max_pages_per_slot), np.int32)
                    if self.paged else None)
        self._bt_dev = None
        self._bt_dirty = True
        self._slot_pages: list[list[int]] = [[] for _ in range(num_slots)]
        # Device-side length each slot's dispatched work will have reached
        # (insert length + every dispatched chunk step): what page growth is
        # planned against.
        self._slot_disp: list[int] = [0] * num_slots
        # Preempted requests wait here and are re-admitted BEFORE anything
        # in _pending — a preempted request resumes ahead of new admissions.
        from collections import deque as _deque

        self._resume: "Any" = _deque()
        self._pending: queue.Queue[Request] = queue.Queue()
        self._next_id = 0   # guarded-by: _lock
        self._lock = sanitize.lock("ServingEngine._lock", hot=True)
        # Work signal for the engine loop: notified on submit and stop so
        # the idle loop wakes immediately instead of sleep-polling
        # (KUKE009). Shares _lock — the predicate it waits on
        # (_pending_n, slot occupancy) is _lock-guarded state.
        self._work = sanitize.condition(self._lock,
                                        name="ServingEngine._work")
        self._running = False   # guarded-by: _lock
        self._thread: threading.Thread | None = None
        self.error: Exception | None = None   # last engine-loop failure
        # Admission control: with max_pending set, submit() sheds (raises
        # RejectedError) once that many requests are queued but not yet
        # slotted — bounded memory and bounded queueing delay instead of an
        # unbounded backlog that OOMs or serves nobody within deadline.
        # _pending_n is the exact count of admitted-not-yet-slotted requests
        # (queue.qsize() is wrong during the sweep's drain-and-refill).
        self.max_pending = max_pending
        self._pending_n = 0   # guarded-by: _lock
        self.retry_after_s = 1.0

        # --- observability (obs/) -------------------------------------
        # Per-engine registry by default: tests and multi-engine processes
        # must never cross-pollute; the serving cell injects its own so
        # cell-level and engine-level metrics share one /metrics scrape.
        self.registry = registry or Registry()
        self.tracer = Tracer(capacity=512)
        reg = self.registry
        self._m_queue_wait = reg.histogram(
            "kukeon_engine_queue_wait_seconds",
            "Submit -> dequeued-for-a-slot wait.")
        self._m_prefill_tokens = reg.counter(
            "kukeon_engine_prefill_tokens_total",
            "Prompt tokens by prefill dispatch: real = run through the "
            "model (the tail on a prefix hit), padded = the bucket they ran "
            "in, cached = rows taken from the prefix store.",
            labels=("kind",))
        self._m_steps = reg.counter(
            "kukeon_engine_steps_total",
            "Engine-loop steps that did work.")
        self._m_chunks = reg.counter(
            "kukeon_engine_decode_chunks_total",
            "Dispatched decode chunks, by their length in steps.",
            labels=("k",))
        self._m_state_steps = reg.counter(
            "kukeon_engine_state_slot_steps_total",
            "Slot states (arrays without a row axis: a state-space layer's) "
            "by dispatched decode chunk, over its steps and every kind that "
            "holds one: held = what the program reads and writes, every "
            "slot's; active = those of the slots that were decoding.",
            labels=("what",))
        self._m_kv_rows = reg.counter(
            "kukeon_engine_decode_kv_rows_total",
            "Cache rows by dispatched decode chunk, over its steps and "
            "every layer: held = what the slots hold, read = what the "
            "attention fetches for the active slots (whole blocks of live "
            "rows where the kernel runs, else all of held).",
            labels=("what",))
        # What a layered family's forwards sum on the device (an expert
        # layer's routed choices and those that chose a held expert): they
        # come back in the fetches of first tokens and chunk blocks.
        self._counted = tuple(
            reg.counter(name, "Summed on the device by the model's forwards "
                        "over real prompt tokens and active slots.")
            for name in (self._layered.counters(cfg) if self._layered
                         else ()))
        # The loop's spans (on the profiler's clock while /v1/profile
        # captures) and its wall time by phase (obs/spans.py).
        self.spans = LoopSpans(reg)
        self._m_ttft = reg.histogram(
            "kukeon_engine_ttft_seconds",
            "Submit -> first token emitted (time to first token).")
        self._m_itl = reg.histogram(
            "kukeon_engine_inter_token_seconds",
            "Gap between consecutive emitted tokens of one request.")
        self._m_e2e = reg.histogram(
            "kukeon_engine_e2e_seconds",
            "Submit -> terminal event (any outcome).")
        self._m_tokens = reg.counter(
            "kukeon_engine_tokens_total", "Tokens emitted.")
        self._m_requests = reg.counter(
            "kukeon_engine_requests_total",
            "Requests reaching a terminal event, by outcome.",
            labels=("outcome",))
        self._m_shed = reg.counter(
            "kukeon_engine_shed_total",
            "Load-shedding events (rejected = queue full at submit, "
            "timed_out = deadline expired).", labels=("reason",))
        # The PR-2 shed dict is now a registry view (same keys, same reads;
        # kv_exhausted joined with the paged allocator — a request shed
        # because the KV page pool ran dry with nothing reclaimable).
        self.shed_stats = _CounterMapView(
            self._m_shed, "reason", ("rejected", "timed_out", "kv_exhausted"))
        # Paged-KV telemetry. Families are declared in every mode so the
        # scrape schema is stable; a legacy engine reports a 0-page pool.
        reg.gauge("kukeon_kv_pages_total",
                  "Usable KV pool pages (0 = legacy contiguous layout)."
                  ).set(self.kv_pool_pages)
        reg.gauge("kukeon_kv_pages_in_use",
                  "KV pool pages currently allocated.").set_function(
            lambda: float(self._pool.in_use) if self._pool else 0.0)
        reg.gauge("kukeon_kv_prefix_shared_pages",
                  "Distinct pool pages pinned by prefix-cache entries "
                  "(shared read-only across sessions).").set_function(
            self._prefix_shared_pages)
        self._m_preempt = reg.counter(
            "kukeon_preemptions_total",
            "In-flight requests preempted (pages reclaimed, request "
            "requeued ahead of new admissions), by reason.",
            labels=("reason",))
        reg.gauge("kukeon_engine_mesh_chips",
                  "Devices in this engine's serving mesh (1 = single-chip; "
                  "> 1 = tensor-parallel sharded programs and KV pool)."
                  ).set(mesh.size)
        reg.gauge("kukeon_engine_slots_total",
                  "Decode slots in the fixed batch.").set(num_slots)
        reg.gauge("kukeon_engine_slots_free",
                  "Slots with no active request.").set_function(
            lambda: len(self._free_slots()))
        reg.gauge("kukeon_engine_queue_depth",
                  "Requests waiting for a slot (admitted-not-yet-slotted "
                  "plus preempted-awaiting-resume).").set_function(
            lambda: self._pending_n + len(self._resume))
        reg.gauge("kukeon_engine_max_pending",
                  "Admission bound (-1 = unbounded).").set(
            -1 if max_pending is None else max_pending)
        # Transfer/prefix-cache counters surface at scrape time from the
        # live dicts (zero extra work on the decode hot path — the roofline
        # budget in test_decode_host_sync_budget stays untouched). The
        # fault-point family rides along: most fault seams live in this
        # module, so an engine scrape is complete without a cell wrapper.
        reg.register_collector(self._obs_collect)
        reg.register_collector(faults_collector)
        # Device-level telemetry (obs/device.py): HBM gauges read from
        # jax.Device.memory_stats() at scrape time, and compile tracking
        # around the jitted programs — the docstring's "occupancy changes
        # never recompile" promise is a measurable invariant
        # (kukeon_compiles_total{program="decode"} flat after warmup; a
        # tier-1 test asserts it across slot churn).
        reg.register_collector(device_memory_collector)
        # Which kernel path (Pallas or XLA) the dispatching ops chose
        # while this process's programs were traced (ops/dispatch.py).
        reg.register_collector(op_impl_collector)
        self.compiles = CompileTracker(reg)
        # Roofline instruments (obs/profile.py): per-program dispatch
        # timers settled inside the counted _fetch seam (zero new host
        # syncs — the decode budget tests pass with timers armed), and
        # the step flight recorder the cells expose as /v1/timeline.
        self.timers = ProgramTimers(reg)
        self.recorder = FlightRecorder(registry=reg)
        # Step-local counters the flight recorder snapshots at the end of
        # each working step (driver thread only — no lock needed).
        self._step_tokens = 0
        self._step_preempts = 0
        # Progress heartbeat for the TPU watchdog: bumped on submit and on
        # every step() that did work. A wedged runtime blocks the driver
        # inside a device call, so this goes stale while work is queued —
        # exactly the signal stalled_s() exposes.
        self.last_progress = time.monotonic()   # guarded-by: _lock

        # Prefix cache: prefix_id -> stored prompt KV (LRU, driver-thread
        # only). Agent sessions re-send a large shared/growing context with
        # every request; reusing its KV turns an O(context) prefill into an
        # O(new tokens) one. Bounded by BOTH entry count and device bytes —
        # HBM is the constrained resource (one 8B entry at 8k context is
        # ~1 GiB of K+V), so the byte budget is what prevents an OOM.
        from collections import OrderedDict

        self._prefix_cache: "OrderedDict[str, _CachedPrefix]" = OrderedDict()
        # A layered family has no prefix store (a ring does not hold a
        # prefix's rows, a state is its prompt's END and no prefix of it):
        # a prefixId is a counted miss and stores nothing.
        self._prefix_cache_size = (0 if self._layered
                                   else max(0, prefix_cache_size))
        self._prefix_cache_bytes = max(0, prefix_cache_bytes)
        self.prefix_hits = 0
        self.prefix_misses = 0

        self._build_programs()

    # --- jitted programs ---------------------------------------------------

    def _cache_shardings(self) -> tuple[NamedSharding, NamedSharding]:
        """(k/v sharding, scale sharding) for the decode cache."""
        spec = shd.kv_cache_spec()
        tensor_size = self.mesh.shape.get(shd.AXIS_TENSOR, 1)
        if (self.kv_shard is False or self._layered
                or self.cfg.num_kv_heads % max(tensor_size, 1)):
            # Replicate the cache when the tuner says so or when the KV
            # heads don't divide the tensor axis (correct, just more HBM)
            # instead of failing device_put.
            spec = PartitionSpec()
        # Scales [L, B, S, KV] shard like k/v minus the head_dim axis.
        return (NamedSharding(self.mesh, spec),
                NamedSharding(self.mesh, PartitionSpec(*spec[:4])))

    def _state_shardings(self) -> DecodeState:
        """NamedSharding mirror of DecodeState — the jitted programs'
        explicit in/out sharding tree. The KV pool (legacy slots or paged
        pool alike) lives over the mesh's tensor axis on its kv-head dim;
        everything host-logical — per-slot lengths, last tokens, active
        flags — is replicated, because the host block table / slot map is
        the source of truth and every chip must see all of it."""
        kv_sh, sc_sh = self._cache_shardings()
        repl = NamedSharding(self.mesh, PartitionSpec())
        if self._layered:
            return DecodeState(
                cache=jax.tree.map(lambda _: repl, self._cache_shapes()),
                tokens=repl, active=repl)
        if not self.paged and len(kv_sh.spec):
            # The contiguous state holds k/v KV-major (_kv_major): the spec's
            # kv-head entry moves with its axis, from position 3 to 2.
            layer, slot, row, head, dim = kv_sh.spec
            kv_sh = NamedSharding(
                self.mesh, PartitionSpec(layer, slot, head, row, dim))
        cache = llama.KVCache(
            k=kv_sh, v=kv_sh, lengths=repl,
            k_scale=sc_sh if self.kv_cache_int8 else None,
            v_scale=sc_sh if self.kv_cache_int8 else None,
        )
        return DecodeState(cache=cache, tokens=repl, active=repl)

    def _cache_shapes(self) -> llama.KVCache:
        """ShapeDtypeStructs of the cache as the state HOLDS it."""
        if self._layered:
            return kv_kinds.shapes(self._kinds, self.num_slots,
                                   self.cfg.num_kv_heads, self.cfg.head_dim,
                                   self.cfg.dtype)
        if self.paged:
            # Pool layout: page axis where the legacy cache has its slot
            # axis ([L, P, page_tokens, KV, D]); lengths stay per-SLOT [B]
            # (the pool has no per-page length — the block table says which
            # pages a slot's logical [0, S_max) range maps to). Page 0 is
            # the scratch page (kv_pages.SCRATCH_PAGE).
            shapes = jax.eval_shape(
                lambda: llama.KVCache.create(
                    self.cfg, self.kv_pool_pages + 1, self.page_tokens,
                    quantized=self.kv_cache_int8,
                )
            )
            return dataclasses.replace(
                shapes,
                lengths=jax.ShapeDtypeStruct((self.num_slots,), jnp.int32))
        shapes = jax.eval_shape(
            lambda: llama.KVCache.create(
                self.cfg, self.num_slots, self.max_seq_len,
                quantized=self.kv_cache_int8,
            )
        )
        return dataclasses.replace(
            shapes, k=jax.eval_shape(_kv_major, shapes.k),
            v=jax.eval_shape(_kv_major, shapes.v))

    def _init_state(self) -> DecodeState:
        # Zeros built in the held shape: one cache live at boot, never two.
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._cache_shapes())
        if self._layered:
            return DecodeState(
                cache=jax.device_put(cache, self._state_shardings().cache),
                tokens=jnp.zeros((self.num_slots,), jnp.int32),
                active=jnp.zeros((self.num_slots,), bool))
        sc_sharding = self._cache_shardings()[1]
        kv_sharding = self._state_shardings().cache.k
        cache = llama.KVCache(
            k=jax.device_put(cache.k, kv_sharding),
            v=jax.device_put(cache.v, kv_sharding),
            lengths=cache.lengths,
            k_scale=(jax.device_put(cache.k_scale, sc_sharding)
                     if cache.k_scale is not None else None),
            v_scale=(jax.device_put(cache.v_scale, sc_sharding)
                     if cache.v_scale is not None else None),
        )
        return DecodeState(
            cache=cache,
            tokens=jnp.zeros((self.num_slots,), jnp.int32),
            active=jnp.zeros((self.num_slots,), bool),
        )

    def _build_layered_programs(self):
        """prefill / insert / decode_chunk of a family whose layers hold
        several kinds of state, under the names and call signatures of the
        dense ones, so that every dispatch path, ``precompile`` and
        ``warmup`` serve both. The family's forwards return device-summed
        counters beside their logits; they ride in the array the host
        already fetches: a prefill's ``first`` is [token, *counters], a
        chunk's block [B + len(counters), K] (a counter's row a step).

        What a prefill leaves behind is ONE tree, shaped as the family's
        kinds state it (``kv_kinds.names``: K and V blocks, state arrays);
        between the two programs it travels as its leaves, in that order, in
        the place of the dense path's ``kv_k, kv_v``."""
        cfg, kinds, model = self.cfg, self._kinds, self._layered
        names = kv_kinds.names(kinds)

        def prefill(params, tokens, length, key, temp, top_k, top_p):
            last, block, counted = model.prefill(params, cfg, tokens, length)
            first = sample_per_slot(
                last[None, :], key, temp[None], top_k[None], top_p[None])[0]
            if self._counted:
                first = jnp.concatenate([first[None], counted])
            return (first, *(block[name] for name in names))

        def insert(state: DecodeState, *args):
            *block, length, slot, token = args
            token = jnp.reshape(token, (-1,))[0]
            return DecodeState(
                cache=kv_kinds.insert(state.cache, kinds,
                                      dict(zip(names, block)), length, slot),
                tokens=state.tokens.at[slot].set(token),
                active=state.active.at[slot].set(True))

        def decode_chunk_fn(params, state: DecodeState, key, temps, top_ks,
                            top_ps, n_steps):
            def body(carry, _):
                state, key = carry
                logits, new, counted = model.decode(
                    params, cfg, state.tokens, state.cache, kinds,
                    state.active)
                # appends at the slots' lengths; inactive slots' lengths stay
                cache = kv_kinds.append(state.cache, kinds, new, state.active)
                key, k1 = jax.random.split(key)
                next_tokens = sample_per_slot(logits, k1, temps, top_ks,
                                              top_ps)
                next_tokens = jnp.where(state.active, next_tokens,
                                        state.tokens)
                return (DecodeState(cache=cache, tokens=next_tokens,
                                    active=state.active), key), (
                    next_tokens, counted)

            def swapped(state):     # held KV-major <-> the row-major view
                return dataclasses.replace(
                    state, cache=kv_kinds.view(state.cache))

            (state, _), (toks, counted) = jax.lax.scan(
                body, (swapped(state), key), length=n_steps)
            return swapped(state), jnp.concatenate([toks.T, counted.T])

        ct, tm = self.compiles, self.timers
        p_sh, st_sh = self._shardings, self._state_shardings()
        repl = NamedSharding(self.mesh, PartitionSpec())
        self._prefill = ct.wrap(jax.jit(
            prefill,
            in_shardings=(p_sh, repl, repl, repl, repl, repl, repl),
            out_shardings=(repl,) * (1 + len(names)),
        ), "prefill", timer=tm.track("prefill"))
        self._insert = ct.wrap(jax.jit(
            insert, donate_argnums=(0,),
            in_shardings=(st_sh,) + (repl,) * (len(names) + 3),
            out_shardings=st_sh,
        ), "insert", timer=tm.track("insert"))
        self._decode_chunk = ct.wrap(jax.jit(
            decode_chunk_fn, static_argnums=(6,), donate_argnums=(1,),
            in_shardings=(p_sh, st_sh, repl, repl, repl, repl),
            out_shardings=(st_sh, repl),
        ), "decode", timer=tm.track("decode_chunk"))

    def _build_programs(self):
        if self._layered:
            return self._build_layered_programs()
        cfg = self.cfg
        fwd = self._forward
        last_pos_ok = self._fwd_logit_positions
        tells_active = self._fwd_active

        def served(state: DecodeState) -> dict:
            """The decode step's word on which slots it serves: a slot that
            is not active reads no cache row (decided here, inside the
            jitted chunk, from the state's own flags)."""
            return {"active": state.active} if tells_active else {}

        def last_logits(params, tokens, positions, cache, length):
            """(last-position logits [V], cache') — via the forward's
            single-position LM head when it has one (prefill then never
            materializes the [S_bucket, V] f32 logits block), else by
            slicing the full logits."""
            if last_pos_ok:
                logits, cache = fwd(
                    params, cfg, tokens, positions, cache,
                    logit_positions=jnp.reshape(length - 1, (1,)),
                )
                return logits[0, 0], cache
            logits, cache = fwd(params, cfg, tokens, positions, cache)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, keepdims=False)
            return last, cache

        def prefill(params, tokens, length, key, temp, top_k, top_p):
            """tokens [1, S_bucket] -> (first sampled token, kv block)."""
            S = tokens.shape[1]
            positions = jnp.arange(S, dtype=jnp.int32)[None, :]
            cache = llama.KVCache.create(cfg, 1, S)
            last, cache = last_logits(params, tokens, positions, cache, length)
            first = sample_per_slot(
                last[None, :], key, temp[None], top_k[None], top_p[None]
            )[0]
            return first, cache.k, cache.v

        def prefill_ext(params, kv_k, kv_v, plen, tokens, length, key,
                        temp, top_k, top_p):
            """Prefill a suffix against a pre-seeded prefix KV block.

            kv_k/kv_v: [L, 1, Pb, KV, D] stored prefix (Pb bucketed, first
            ``plen`` rows valid); tokens: [1, S_tail] at positions
            plen..plen+S_tail-1. The tail's K/V overwrite rows starting at
            plen; rows past plen+length are masked by kv_length. Returns
            (first sampled token, full kv block [L, 1, Pb+S_tail, ...])."""
            S = tokens.shape[1]
            Pb = kv_k.shape[2]
            base = llama.KVCache.create(cfg, 1, Pb + S)
            cache = llama.KVCache(
                k=jax.lax.dynamic_update_slice(base.k, kv_k, (0, 0, 0, 0, 0)),
                v=jax.lax.dynamic_update_slice(base.v, kv_v, (0, 0, 0, 0, 0)),
                lengths=jnp.full((1,), plen, jnp.int32),
            )
            positions = plen + jnp.arange(S, dtype=jnp.int32)[None, :]
            last, cache = last_logits(params, tokens, positions, cache, length)
            first = sample_per_slot(
                last[None, :], key, temp[None], top_k[None], top_p[None]
            )[0]
            # Re-bucket the output block to a CANONICAL shape inside the
            # program (shapes are static at trace time): without this, a
            # growing conversation would mint a new (Pb, S) pair — and a
            # fresh full-model compile — every turn, and an eager reshape
            # on a GSPMD-sharded output can hit unparseable named-sharding
            # conversions. Canonical shapes keep the (Pb, S_tail) compile
            # set small and shared with the miss path's insert shapes.
            out_S = min(self._bucket(Pb + S), self.max_seq_len)
            out_k, out_v = cache.k, cache.v
            if Pb + S > out_S:
                out_k = out_k[:, :, :out_S]
                out_v = out_v[:, :, :out_S]
            elif Pb + S < out_S:
                pad = [(0, 0), (0, 0), (0, out_S - (Pb + S)), (0, 0), (0, 0)]
                out_k = jnp.pad(out_k, pad)
                out_v = jnp.pad(out_v, pad)
            return first, out_k, out_v

        @jax.named_scope("kv_insert")
        def insert(state: DecodeState, kv_k, kv_v, length, slot, token):
            """Copy a prefill's KV block into ``slot`` and activate it.

            Prefill produces full-precision K/V (its self-attention is
            exact); a quantized state cache quantizes the block here, once,
            as it lands in the slot."""
            ks = vs = None
            if state.cache.quantized:
                kv_k, ks = llama.quantize_kv(kv_k)   # [L, 1, S, KV(, D)]
                kv_v, vs = llama.quantize_kv(kv_v)
            k = jax.lax.dynamic_update_slice(
                state.cache.k, _kv_major(kv_k), (0, slot, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(
                state.cache.v, _kv_major(kv_v), (0, slot, 0, 0, 0))
            cache = llama.KVCache(
                k=k, v=v, lengths=state.cache.lengths.at[slot].set(length),
                k_scale=(jax.lax.dynamic_update_slice(
                    state.cache.k_scale, ks, (0, slot, 0, 0))
                    if ks is not None else state.cache.k_scale),
                v_scale=(jax.lax.dynamic_update_slice(
                    state.cache.v_scale, vs, (0, slot, 0, 0))
                    if vs is not None else state.cache.v_scale),
            )
            return DecodeState(
                cache=cache,
                tokens=state.tokens.at[slot].set(token),
                active=state.active.at[slot].set(True),
            )

        def decode_chunk_fn(params, state: DecodeState, key, temps, top_ks, top_ps, n_steps):
            """K decode steps in one on-device scan -> tokens [B, K].

            Sampling parameters are dynamic per-slot arrays, so any mix of
            greedy/temperature/top-k/top-p requests shares this one program.
            """

            def body(carry, _):
                state, key = carry
                tokens = state.tokens[:, None]
                lengths_before = state.cache.lengths
                positions = lengths_before[:, None]
                logits, cache = fwd(
                    params, cfg, tokens, positions, state.cache,
                    **served(state)
                )
                # Inactive slots must not advance their cache length.
                cache = dataclasses.replace(
                    cache,
                    lengths=jnp.where(state.active, cache.lengths, lengths_before),
                )
                key, k1 = jax.random.split(key)
                next_tokens = sample_per_slot(logits[:, 0, :], k1, temps, top_ks, top_ps)
                next_tokens = jnp.where(state.active, next_tokens, state.tokens)
                new_state = DecodeState(
                    cache=cache, tokens=next_tokens, active=state.active
                )
                return (new_state, key), next_tokens

            def swapped(state):
                # held KV-major <-> the row-major shapes fwd reads (_kv_major)
                return dataclasses.replace(state, cache=dataclasses.replace(
                    state.cache, k=_kv_major(state.cache.k),
                    v=_kv_major(state.cache.v)))

            (state, _), toks = jax.lax.scan(
                body, (swapped(state), key), length=n_steps)
            return swapped(state), toks.T  # [B, K]

        # --- paged variants (block-table gather/scatter) ------------------
        # The pool is [L, P, pt, KV, D]; a slot's logical [0, S_max) range
        # is the concatenation of its block-table pages. All three programs
        # keep static shapes (the block table is always [B, max_pages]), so
        # occupancy churn and page churn never recompile.
        pt_sz = self.page_tokens
        B_slots = self.num_slots
        S_max = self.max_seq_len

        def gather_block(pool_k, pool_v, pool_ks, pool_vs, page_ids):
            """Pool pages -> one dense full-precision block [L, 1, n*pt,
            KV, D] (the prefix-extension prefill's input). Scratch-padded
            page_ids gather garbage rows that the consumer masks by length;
            a quantized pool is dequantized here (f32 product, cast down —
            the same recipe the fused decode path applies)."""
            k = pool_k[:, page_ids]          # [L, n, pt, KV, D]
            v = pool_v[:, page_ids]
            L, n = k.shape[0], page_ids.shape[0]
            k = k.reshape(L, 1, n * pt_sz, *k.shape[3:])
            v = v.reshape(L, 1, n * pt_sz, *v.shape[3:])
            if pool_ks is not None:
                ks = pool_ks[:, page_ids].reshape(L, 1, n * pt_sz, -1)
                vs = pool_vs[:, page_ids].reshape(L, 1, n * pt_sz, -1)
                k = (k.astype(jnp.float32)
                     * ks[..., None].astype(jnp.float32)).astype(cfg.dtype)
                v = (v.astype(jnp.float32)
                     * vs[..., None].astype(jnp.float32)).astype(cfg.dtype)
            return k, v

        @jax.named_scope("kv_insert")
        def insert_paged(state: DecodeState, kv_k, kv_v, length, page_ids,
                         slot, token):
            """Scatter a prefill's [L, 1, Sb, KV, D] block into the pool by
            page index and activate ``slot``.

            page_ids[i] is the pool destination of block rows
            [i*pt, (i+1)*pt) — the host passes SCRATCH_PAGE for pages it
            must not write (shared prefix pages stay read-only, bucket
            padding goes nowhere), so one compiled program per bucket covers
            every share/pad combination."""
            ks = vs = None
            if state.cache.quantized:
                kv_k, ks = llama.quantize_kv(kv_k)
                kv_v, vs = llama.quantize_kv(kv_v)
            L = kv_k.shape[0]
            nb = page_ids.shape[0]
            cache = state.cache
            new_k = cache.k.at[:, page_ids].set(
                kv_k.reshape(L, nb, pt_sz, *kv_k.shape[3:]))
            new_v = cache.v.at[:, page_ids].set(
                kv_v.reshape(L, nb, pt_sz, *kv_v.shape[3:]))
            k_scale, v_scale = cache.k_scale, cache.v_scale
            if ks is not None:
                k_scale = k_scale.at[:, page_ids].set(
                    ks.reshape(L, nb, pt_sz, -1))
                v_scale = v_scale.at[:, page_ids].set(
                    vs.reshape(L, nb, pt_sz, -1))
            cache = llama.KVCache(
                k=new_k, v=new_v,
                lengths=cache.lengths.at[slot].set(length),
                k_scale=k_scale, v_scale=v_scale,
            )
            return DecodeState(
                cache=cache,
                tokens=state.tokens.at[slot].set(token),
                active=state.active.at[slot].set(True),
            )

        def decode_chunk_paged(params, state: DecodeState, bt, key, temps,
                               top_ks, top_ps, n_steps):
            """K decode steps over the paged pool, dense-view pipelined:
            gather every slot's pages into the [L, B, S_max, KV, D] view
            the model forward already speaks ONCE, run the whole chunk on
            that view (the exact per-step cost of the legacy layout), then
            scatter the chunk's new K/V rows back to their (page, offset)
            homes in one flattened vectorized write. Amortizing the
            gather/scatter over K steps is what keeps the paged layout's
            per-token cost at parity with the contiguous one; the dense
            view is a transient buffer that lives only for the chunk —
            persistent HBM is still just the page pool.

            Inactive slots' lengths never advance, and released slots'
            block tables are zeroed host-side, so their stray write-back
            rows flat-map into the scratch page (duplicate scratch
            destinations are harmless — nobody reads scratch) — never
            into a page that was re-issued to another request."""
            pool = state.cache
            L = pool.k.shape[0]
            start_lengths = pool.lengths
            view_k = pool.k[:, bt].reshape(
                L, B_slots, S_max, *pool.k.shape[3:])
            view_v = pool.v[:, bt].reshape(
                L, B_slots, S_max, *pool.v.shape[3:])
            vks = vvs = None
            if pool.quantized:
                vks = pool.k_scale[:, bt].reshape(L, B_slots, S_max, -1)
                vvs = pool.v_scale[:, bt].reshape(L, B_slots, S_max, -1)
            view = llama.KVCache(k=view_k, v=view_v, lengths=start_lengths,
                                 k_scale=vks, v_scale=vvs)
            vstate = DecodeState(cache=view, tokens=state.tokens,
                                 active=state.active)

            def body(carry, _):
                st, key = carry
                tokens = st.tokens[:, None]
                lengths_before = st.cache.lengths
                positions = lengths_before[:, None]
                logits, cache = fwd(params, cfg, tokens, positions, st.cache,
                                    **served(st))
                # Inactive slots must not advance their cache length.
                cache = dataclasses.replace(
                    cache,
                    lengths=jnp.where(st.active, cache.lengths,
                                      lengths_before),
                )
                key, k1 = jax.random.split(key)
                next_tokens = sample_per_slot(
                    logits[:, 0, :], k1, temps, top_ks, top_ps)
                next_tokens = jnp.where(st.active, next_tokens, st.tokens)
                new_state = DecodeState(cache=cache, tokens=next_tokens,
                                        active=st.active)
                return (new_state, key), next_tokens

            (vstate, _), toks = jax.lax.scan(body, (vstate, key),
                                             length=n_steps)

            # Write-back: row t of slot b (absolute position
            # start_lengths[b] + t) lands at flat pool row
            # bt[b, pos // pt] * pt + pos % pt. Positions are clamped to
            # the view bound for slots frozen near S_max — their zeroed /
            # stale table rows route the write to scratch anyway.
            bidx = jnp.arange(B_slots)
            pos = jnp.minimum(
                start_lengths[:, None] + jnp.arange(n_steps)[None, :],
                S_max - 1,
            )                                                  # [B, K]
            page = bt[bidx[:, None],
                      jnp.minimum(pos // pt_sz, bt.shape[1] - 1)]
            dest = (page * pt_sz + pos % pt_sz).reshape(-1)    # [B*K]
            rows_k = vstate.cache.k[:, bidx[:, None], pos]     # [L, B, K, ...]
            rows_v = vstate.cache.v[:, bidx[:, None], pos]
            pk = pool.k.reshape(L, -1, *pool.k.shape[3:]).at[:, dest].set(
                rows_k.reshape(L, -1, *rows_k.shape[3:])
            ).reshape(pool.k.shape)
            pv = pool.v.reshape(L, -1, *pool.v.shape[3:]).at[:, dest].set(
                rows_v.reshape(L, -1, *rows_v.shape[3:])
            ).reshape(pool.v.shape)
            pks, pvs = pool.k_scale, pool.v_scale
            if pks is not None:
                rows_ks = vstate.cache.k_scale[:, bidx[:, None], pos]
                rows_vs = vstate.cache.v_scale[:, bidx[:, None], pos]
                pks = pks.reshape(L, -1, pks.shape[3]).at[:, dest].set(
                    rows_ks.reshape(L, -1, rows_ks.shape[3])
                ).reshape(pool.k_scale.shape)
                pvs = pvs.reshape(L, -1, pvs.shape[3]).at[:, dest].set(
                    rows_vs.reshape(L, -1, rows_vs.shape[3])
                ).reshape(pool.v_scale.shape)
            new_cache = llama.KVCache(k=pk, v=pv,
                                      lengths=vstate.cache.lengths,
                                      k_scale=pks, v_scale=pvs)
            new_state = DecodeState(cache=new_cache, tokens=vstate.tokens,
                                    active=state.active)
            return new_state, toks.T  # [B, K]

        # Every program dispatches through the compile tracker: a dispatch
        # that grew the jit tracing cache is counted + timed by program
        # (prefill covers both the cold and prefix-extend variants). The
        # wrapper forwards .lower/.compile so precompile() is unchanged.
        #
        # Every jit names explicit in/out shardings (KUKE014): params by
        # the model's PartitionSpec tree, KV blocks and the pool over the
        # mesh's tensor axis (kv-head dim), and everything host-shaped —
        # tokens, lengths, RNG keys, sampling arrays, block tables —
        # replicated. On a 1-chip mesh these degenerate to the one device;
        # on an N-chip mesh they make the layout a statement rather than a
        # GSPMD inference, so the paged pool is *placed* where
        # _init_state put it and donation reuses the sharded buffers.
        ct = self.compiles
        tm = self.timers
        p_sh = self._shardings
        st_sh = self._state_shardings()
        kv_sh, sc_sh = self._cache_shardings()
        repl = NamedSharding(self.mesh, PartitionSpec())
        # Every wrap registers with BOTH seams: the coarse compile label
        # (prefill|insert|decode — the benchmark's compiles_in_window and
        # the compile-flat tests consume that vocabulary, do not change it)
        # and the per-program timer (kukelint KUKE015 requires the timer=
        # keyword).
        self._prefill = ct.wrap(jax.jit(
            prefill,
            in_shardings=(p_sh, repl, repl, repl, repl, repl, repl),
            out_shardings=(repl, kv_sh, kv_sh),
        ), "prefill", timer=tm.track("prefill"))
        self._prefill_ext = ct.wrap(jax.jit(
            prefill_ext,
            in_shardings=(p_sh, kv_sh, kv_sh, repl, repl, repl, repl,
                          repl, repl, repl),
            out_shardings=(repl, kv_sh, kv_sh),
        ), "prefill", timer=tm.track("prefill_ext"))
        self._insert = ct.wrap(jax.jit(
            insert, donate_argnums=(0,),
            in_shardings=(st_sh, kv_sh, kv_sh, repl, repl, repl),
            out_shardings=st_sh,
        ), "insert", timer=tm.track("insert"))
        self._decode_chunk = ct.wrap(jax.jit(
            decode_chunk_fn, static_argnums=(6,), donate_argnums=(1,),
            in_shardings=(p_sh, st_sh, repl, repl, repl, repl),
            out_shardings=(st_sh, repl),
        ), "decode", timer=tm.track("decode_chunk"))
        self._gather_block = ct.wrap(jax.jit(
            gather_block,
            in_shardings=(kv_sh, kv_sh, sc_sh, sc_sh, repl),
            out_shardings=(kv_sh, kv_sh),
        ), "prefill", timer=tm.track("gather_block"))
        self._insert_paged = ct.wrap(jax.jit(
            insert_paged, donate_argnums=(0,),
            in_shardings=(st_sh, kv_sh, kv_sh, repl, repl, repl, repl),
            out_shardings=st_sh,
        ), "insert", timer=tm.track("insert_paged"))
        self._decode_chunk_paged = ct.wrap(jax.jit(
            decode_chunk_paged, static_argnums=(7,), donate_argnums=(1,),
            in_shardings=(p_sh, st_sh, repl, repl, repl, repl, repl),
            out_shardings=(st_sh, repl),
        ), "decode", timer=tm.track("decode_chunk_paged"))

    def _bucket(self, n: int) -> int:
        return bucket_length(n, self.prefill_buckets)

    def _fetch(self, x) -> np.ndarray:
        """Blocking device→host readback, counted and timed (the roofline
        budget is ≤1 per decode chunk — tests/test_serving.py asserts it
        here)."""
        faults.maybe_fail("engine.fetch")
        sanitize.blocking("engine._fetch device transfer")
        t0 = time.monotonic()
        out = np.asarray(x)
        self.sync_stats["fetches"] += 1
        self.sync_stats["fetch_s"] += time.monotonic() - t0
        # Retire pending program-timer marks: device execution is in
        # dispatch order, so everything enqueued before the array we just
        # materialized is complete — the readiness probes below are
        # non-blocking and this stays the budget's ≤1 sync per chunk.
        self.timers.settle()
        return out

    def _upload(self, x, sharding=None):
        """Host→device array upload, counted and timed. ``sharding`` routes
        the upload through a per-leaf sharded device_put — the streamed
        checkpoint path's placement primitive; plain serving-path uploads
        keep the default-device jnp.asarray."""
        faults.maybe_fail("engine.upload")
        sanitize.blocking("engine._upload device transfer")
        t0 = time.monotonic()
        if sharding is None:
            out = jnp.asarray(x)
        else:
            out = jax.device_put(x, sharding)
        self.sync_stats["uploads"] += 1
        self.sync_stats["upload_s"] += time.monotonic() - t0
        return out

    def _consume_stream(self, stream):
        """Drain a CheckpointStream into the device param tree: each leaf
        goes through the counted _upload seam with its own NamedSharding
        the moment its bytes arrive off disk, so tensor i+1's read (the
        stream's reader threads) overlaps tensor i's device transfer.
        Raises the stream's CheckpointStreamError through to _load_exc —
        a half-streamed boot fails clean, it never serves."""
        from kukeon_tpu.models.checkpoints import _walk_tree

        flat_sh = dict(_walk_tree(self._shardings))
        flat: dict[tuple, Any] = {}
        for path, arr in stream:
            t0 = time.monotonic()
            flat[path] = self._upload(arr, sharding=flat_sh[path])
            self.load_stats["upload_s"] += time.monotonic() - t0
            self.load_stats["bytes"] += arr.nbytes
            self.load_stats["tensors"] += 1
        tree: dict = {}
        for path, leaf in flat.items():
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = leaf
        return tree

    def _obs_collect(self):
        """Scrape-time counter families sourced from the live dicts the
        hot path already maintains (sync_stats is bumped inside _fetch /
        _upload with no lock; mirroring it here instead of double-counting
        keeps the decode loop's instrumentation overhead at zero)."""
        s = self.sync_stats
        yield ("kukeon_engine_host_sync_total", "counter",
               "Blocking host<->device transfers (fetch = device->host "
               "readback, upload = host->device array).",
               [({"kind": "fetch"}, float(s["fetches"])),
                ({"kind": "upload"}, float(s["uploads"]))])
        yield ("kukeon_engine_host_sync_seconds_total", "counter",
               "Wall time spent blocked in host<->device transfers.",
               [({"kind": "fetch"}, float(s["fetch_s"])),
                ({"kind": "upload"}, float(s["upload_s"]))])
        lens = [n for n, req in zip(self._slot_len, self._slot_req)
                if req is not None]
        yield ("kukeon_engine_kv_rows", "gauge",
               "Rows the seated slots hold in one layer of each kind (a "
               "window layer's ring holds at most its window); of a kind "
               "that holds state without rows, the slots that hold one.",
               [({"kind": kd.name}, float(sum(kd.live(n) for n in lens)))
                for kd in self._kinds])
        # Streamed-checkpoint boot pipeline accounting: per-stage wall time
        # (stages OVERLAP — their sum exceeds the load's wall clock by
        # design) and bytes moved. All-zero on a non-streamed boot.
        ls = self.load_stats
        cs = (self._ckpt_stream.stat_snapshot()
              if self._ckpt_stream is not None else {})
        yield ("kukeon_checkpoint_load_bytes_total", "counter",
               "Checkpoint bytes streamed host->device during boot.",
               [({}, float(max(int(cs.get("bytes", 0)), ls["bytes"])))])
        yield ("kukeon_checkpoint_load_seconds", "counter",
               "Streamed checkpoint load wall time by pipeline stage "
               "(disk = reader-thread file reads, cast = host dtype "
               "casts/quantize, upload = sharded device_put). Stages run "
               "concurrently: their sum exceeds the load wall clock.",
               [({"stage": "disk"}, float(cs.get("disk_s", 0.0))),
                ({"stage": "cast"}, float(cs.get("cast_s", 0.0))),
                ({"stage": "upload"}, float(ls["upload_s"]))])
        yield ("kukeon_engine_prefix_cache_total", "counter",
               "Prefix-KV cache lookups by result.",
               [({"result": "hit"}, float(self.prefix_hits)),
                ({"result": "miss"}, float(self.prefix_misses))])
        ss = self.tracer.sample_stats
        yield ("kukeon_trace_tail_sampled_total", "counter",
               "Tail-sampler verdicts on finished trace spans (error/"
               "preempted/retried/slow spans are always kept).",
               [({"decision": "kept"}, float(ss["kept"])),
                ({"decision": "dropped"}, float(ss["dropped"]))])

    def _observe_terminal(self, req: Request, outcome: str) -> None:
        """Record a request's terminal event on every instrument at once:
        e2e histogram, outcome counter, trace span, correlated log line.
        Exactly one terminal per request — callers run on the driver
        thread (or hold the failure path), and Tracer.finish is idempotent
        so a double-fault keeps the first verdict."""
        if req.submitted_at:
            self._m_e2e.observe(
                time.monotonic() - req.submitted_at,
                exemplar=(req.trace.trace_id
                          if req.trace is not None else None))
        self._m_requests.inc(outcome=outcome)
        if req.trace is not None:
            self.tracer.finish(
                req.trace, outcome, tokens=len(req.generated),
                error=(f"{type(req.error).__name__}: {req.error}"
                       if req.error is not None else None),
            )
        _LOG.debug("request %d %s (%d tokens)", req.id, outcome,
                   len(req.generated),
                   extra={"request_id": req.id, "phase": outcome,
                          "trace_id": (req.trace.trace_id
                                       if req.trace is not None else None)})

    def _ensure_loaded(self):
        """Block until the (possibly async) weight transfer finished."""
        if not self._loaded.is_set():
            self._loaded.wait()
        if self._load_exc is not None:
            raise RuntimeError("engine weight load failed") from self._load_exc

    def _abstract_state(self) -> DecodeState:
        """ShapeDtypeStruct mirror of _init_state (no device bytes)."""
        shapes = self._cache_shapes()
        repl = NamedSharding(self.mesh, PartitionSpec())

        def sds(x, sh):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

        B = self.num_slots
        if self._layered:
            return DecodeState(
                cache=jax.tree.map(lambda x: sds(x, repl), shapes),
                tokens=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl),
                active=jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=repl))
        sc_sh = self._cache_shardings()[1]
        kv_sh = self._state_shardings().cache.k
        cache = llama.KVCache(
            k=sds(shapes.k, kv_sh), v=sds(shapes.v, kv_sh),
            lengths=sds(shapes.lengths, repl),
            k_scale=(sds(shapes.k_scale, sc_sh)
                     if shapes.k_scale is not None else None),
            v_scale=(sds(shapes.v_scale, sc_sh)
                     if shapes.v_scale is not None else None),
        )
        return DecodeState(
            cache=cache,
            tokens=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl),
            active=jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=repl),
        )

    def precompile(self, prompt_lens: tuple[int, ...] = (64,)):
        """AOT-compile the engine's programs from shapes alone — no weights
        needed, so with ``async_load`` this runs WHILE the multi-GB param
        transfer streams in the background and the cold boot pays
        max(transfer, compile) instead of their sum. The compiled
        executables land in the persistent compilation cache; the first
        real dispatch is then a cache hit, not a compile.
        """
        aparams = self._abstract_params
        astate = self._abstract_state()
        cfg = self.cfg
        B = self.num_slots
        key = jax.random.key(0)
        temps = jnp.zeros((B,), jnp.float32)
        top_ks = jnp.zeros((B,), jnp.int32)
        top_ps = jnp.ones((B,), jnp.float32)

        with jax.set_mesh(self.mesh):
            buckets = sorted({
                min(self._bucket(max(1, n)), self.max_seq_len)
                for n in prompt_lens
            })
            for L in buckets:
                tokens = jax.ShapeDtypeStruct((1, L), jnp.int32)
                lowered = self._prefill.lower(
                    aparams, tokens, L // 2, key,
                    jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
                )
                lowered.compile()
                kv_shape = (cfg.num_layers, 1, L, cfg.num_kv_heads, cfg.head_dim)
                kv = jax.ShapeDtypeStruct(kv_shape, cfg.dtype)
                # what the prefill hands insert: a layered family's block
                # is its own (the leaves after the first token)
                block = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                         for o in lowered.out_info[1:]
                         ] if self._layered else [kv, kv]
                if self.paged:
                    ids = jax.ShapeDtypeStruct((L // self.page_tokens,),
                                               jnp.int32)
                    self._insert_paged.lower(
                        astate, kv, kv, L // 2, ids, 0, jnp.int32(1),
                    ).compile()
                else:
                    self._insert.lower(
                        astate, *block, L // 2, 0,
                        jnp.zeros((1 + len(self._counted),), jnp.int32)
                        if self._counted else jnp.int32(1),
                    ).compile()
            chunk_sizes = {1, 4}
            size = 1
            while size * 4 <= self.decode_chunk:
                size *= 4
                chunk_sizes.add(size)
            bt = jax.ShapeDtypeStruct(
                (B, self.max_pages_per_slot), jnp.int32)
            for k in sorted(chunk_sizes):
                if self.paged:
                    self._decode_chunk_paged.lower(
                        aparams, astate, bt, key, temps, top_ks, top_ps, k,
                    ).compile()
                else:
                    self._decode_chunk.lower(
                        aparams, astate, key, temps, top_ks, top_ps, k,
                    ).compile()

    # --- public API --------------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray | list[int],
        sampling: SamplingParams | None = None,
        emit: Callable[[int, bool], None] | None = None,
        prefix_id: str | None = None,
        deadline_s: float | None = None,
        trace_ctx: "Any | None" = None,
        export: bool = False,
        kv_import: "dict | None" = None,
    ) -> Request:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if prompt.size >= self.max_seq_len:
            raise ValueError(
                f"prompt length {prompt.size} >= engine max_seq_len {self.max_seq_len}"
            )
        if export and kv_import is not None:
            raise ValueError("a request cannot both export and import KV")
        if self._layered and (export or kv_import is not None):
            raise ValueError(
                f"family {self.family.name!r} has no KV handoff yet: its "
                "layers hold a ring or a state, not the exported block's "
                "rows")
        if kv_import is not None and int(kv_import["length"]) != prompt.size:
            raise ValueError(
                f"kv_import length {kv_import['length']} != prompt length "
                f"{prompt.size} — the imported block must cover exactly the "
                "prompt rows")
        if self.paged and not export:
            need = self._pool.pages_for(int(prompt.size) + 1)
            if need > self._pool.num_pages:
                # Even an empty pool could never hold this prompt: fail at
                # submit like the max_seq_len check — waiting would deadlock.
                raise ValueError(
                    f"prompt needs {need} KV pages but the pool holds "
                    f"{self._pool.num_pages} (kv_page_tokens="
                    f"{self.page_tokens})"
                )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        now = time.monotonic()
        shed_depth = None
        with self._lock:
            if (self.max_pending is not None
                    and self._pending_n >= self.max_pending):
                shed_depth = self._pending_n
            else:
                req = Request(
                    id=self._next_id, prompt=prompt,
                    sampling=sampling or SamplingParams(),
                    emit=emit, submitted_at=now,
                    prefix_id=prefix_id,
                    deadline=(now + deadline_s)
                    if deadline_s is not None else None,
                    export=export, kv_import=kv_import,
                )
                self._next_id += 1
                self._requests[req.id] = req
                self._pending_n += 1
                self.last_progress = now
        if shed_depth is not None:
            # Shed accounting outside the lock: counter + a zero-length
            # trace span (id -1: the request never earned one) so the shed
            # path is visible in /v1/trace, not just as a counter. The
            # span joins the caller's trace when a context came with the
            # request — a gateway retry's shed hop is part of ONE trace.
            self._m_shed.inc(reason="rejected")
            self._m_requests.inc(outcome="shed")
            self.tracer.finish(
                self.tracer.begin(-1, prompt.size, trace_ctx=trace_ctx),
                "shed")
            raise RejectedError(
                f"pending queue full ({shed_depth}/"
                f"{self.max_pending}); shedding load",
                retry_after_s=self.retry_after_s,
            )
        req.trace = self.tracer.begin(req.id, int(prompt.size),
                                      trace_ctx=trace_ctx)
        self._pending.put(req)
        with self._lock:
            # Wake an idle engine loop parked on the work condition.
            self._work.notify()
        return req

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot: fresh admissions plus preempted
        requests parked for resume. The admission bound (max_pending)
        counts only the former — preemption must never cause sheds."""
        return self._pending_n + len(self._resume)

    def stalled_s(self) -> float:
        """Seconds since the engine last made progress WHILE work is
        outstanding; 0.0 when idle (an idle engine is never stalled).

        ``_requests`` is the authoritative unfinished-request map: it also
        covers a request that has left the queue and sits inside its
        prefill dispatch, not yet slotted — where a first-use compile or a
        hung device call stalls the driver while queue depth and slot
        occupancy both read idle."""
        if not self._requests:
            return 0.0
        return max(0.0, time.monotonic() - self.last_progress)

    def generate(self, prompt, sampling: SamplingParams | None = None) -> list[int]:
        """Blocking convenience wrapper: submit + drive until done."""
        req = self.submit(prompt, sampling)
        if self._running:
            req.done.wait()
        else:
            while not req.done.is_set():
                self.step()
        if req.error is not None:
            raise RuntimeError(f"generation failed: {req.error}") from req.error
        return req.generated

    def warmup(self, prompt_len: int, sampling: SamplingParams | None = None):
        """Pre-compile prefill (at prompt_len's bucket), insert, and every
        decode-chunk program, so cold-start cost doesn't hit live traffic.

        Decoding with no active slot is semantically a no-op (inactive slots
        neither advance cache lengths nor change their last token), so the
        chunk programs can be compiled against the live state. Sampling
        parameters are dynamic, so one warmup covers all request mixes.
        """
        self._ensure_loaded()
        sp = sampling or SamplingParams()
        req = self.submit(
            np.ones((max(1, prompt_len),), np.int32),
            dataclasses.replace(sp, max_new_tokens=1),
        )
        while not req.done.is_set():
            self.step()
        # Every chunk size _chunk_size can produce: powers of 4 up to
        # decode_chunk, plus the 4 it takes while a slot is free.
        chunk_sizes = {1, 4}
        size = 1
        while size * 4 <= self.decode_chunk:
            size *= 4
            chunk_sizes.add(size)
        # Through the counted dirty-flag seam (kukelint KUKE002): one
        # _upload of the three sampling arrays, reused across every chunk
        # size, instead of six raw jnp.asarray transfers the budget never
        # saw.
        temps_d, top_ks_d, top_ps_d = self._sampling_dev_arrays()
        with jax.set_mesh(self.mesh):
            for k in sorted(chunk_sizes):
                self._key, k1 = jax.random.split(self._key)
                if self.paged:
                    self.state, _ = self._decode_chunk_paged(
                        self.params, self.state, self._bt_dev_array(), k1,
                        temps_d, top_ks_d, top_ps_d, k,
                    )
                else:
                    self.state, _ = self._decode_chunk(
                        self.params, self.state, k1,
                        temps_d, top_ks_d, top_ps_d, k,
                    )

    def start(self):
        """Run the engine loop on a background thread."""
        with self._lock:
            self._running = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serving-engine"
        )
        self._thread.start()

    def stop(self):
        with self._lock:
            self._running = False
            # Wake an idle loop parked on the work condition NOW; without
            # the notify it would only notice _running on the safety-net
            # wait timeout.
            self._work.notify_all()
        if self._thread:
            self._thread.join(timeout=5)
            self._thread = None

    def _idle_locked(self) -> bool:
        """True when the loop has nothing to do (caller holds _lock):
        no admitted-unslotted requests, no preempted requests parked for
        resume, no active slots, no unflushed inflight chunk. Cancelled
        or expiring queued requests keep _pending_n nonzero until swept,
        so the loop never parks while any request still needs a sweep."""
        return (self._pending_n == 0 and not self._resume
                and self._inflight is None
                and all(r is None for r in self._slot_req))

    def _loop(self):
        while self._running:
            try:
                if not self.step():
                    # Idle: park on the work condition instead of
                    # sleep-polling (KUKE009). submit()/stop() notify; the
                    # timeout is a safety net for wake paths that predate
                    # the signal (nothing correctness-bearing relies on
                    # it — a lost notify only costs one timeout).
                    with self._work:
                        if self._running and self._idle_locked():
                            with self.spans.span("engine.idle_wait"):
                                self._work.wait(timeout=0.05)
            except Exception as e:  # noqa: BLE001 — the engine thread must not die silently
                import traceback

                traceback.print_exc()
                self.error = e
                self._fail_all(e)
                # Keep serving: state may be poisoned, so rebuild it.
                try:
                    with jax.set_mesh(self.mesh):
                        self.state = self._init_state()
                    self._slot_req = [None] * self.num_slots
                    self._slot_len = [0] * self.num_slots
                    self._inflight = None
                    self._sampling_dirty = True
                    if self.paged:
                        # The pool device tensor was rebuilt: every page and
                        # every prefix entry pointing into the old one is
                        # void. Start the allocator over.
                        self._pool = PageAllocator(self.kv_pool_pages,
                                                   self.page_tokens)
                        self._slot_pages = [[] for _ in range(self.num_slots)]
                        self._slot_disp = [0] * self.num_slots
                        self._bt[:] = 0
                        self._bt_dirty = True
                        self._prefix_cache.clear()
                except Exception:  # noqa: BLE001
                    with self._lock:
                        self._running = False
                    raise

    def _fail_request(self, req: Request, exc: Exception) -> None:
        """Fail ONE request (terminal emit + done), tolerating a bad sink."""
        req.error = exc
        with self._lock:
            self._requests.pop(req.id, None)
        self._observe_terminal(req, "error")
        if req.emit:
            try:
                req.emit(-1, True)
            except Exception:  # noqa: BLE001 — a bad sink must not stop the sweep
                pass
        req.done.set()

    def _fail_all(self, exc: Exception):
        """Fail every active + pending request so callers don't hang.

        Streaming consumers block on their emit channel, not on ``done`` —
        each one must receive the terminal (-1, True) event or it waits
        forever (same contract as the cancel paths)."""
        for slot, req in list(self._active_requests()):
            self._slot_req[slot] = None
            if self.paged:
                self._pool.unref(self._slot_pages[slot])
                self._slot_pages[slot] = []
                self._slot_disp[slot] = 0
                self._bt[slot, :] = 0
                self._bt_dirty = True
            self._fail_request(req, exc)
        self._sampling_dirty = True
        while self._resume:
            self._fail_request(self._resume.popleft(), exc)
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            with self._lock:
                self._pending_n -= 1
            self._fail_request(req, exc)

    # --- engine core -------------------------------------------------------

    def _expired(self, req: Request, now: float | None = None) -> bool:
        return (req.deadline is not None
                and (now if now is not None else time.monotonic())
                >= req.deadline)

    def _sweep_cancelled(self) -> bool:
        """Driver-thread cancellation + deadline expiry: release active
        cancelled/expired slots and complete queued ones NOW — a queued
        cancel (or an already-expired request) must not wait for a slot to
        free before its waiter wakes. Runs once per step, i.e. once per
        decode chunk — that is the deadline-check granularity for active
        requests."""
        did = False
        now = time.monotonic()
        for _slot, req in self._active_requests():
            if req.done.is_set():
                continue
            if req.cancelled:
                self._release_slot(req, cancelled=True)
                did = True
            elif self._expired(req, now):
                self._m_shed.inc(reason="timed_out")
                req.timed_out = True
                req.error = DeadlineExceeded(
                    f"request {req.id} deadline exceeded after "
                    f"{now - req.submitted_at:.2f}s "
                    f"({len(req.generated)} tokens generated)"
                )
                self._release_slot(req, timed_out=True)
                did = True
        # Preempted requests parked for resume observe cancellation and
        # deadlines too — a preempted request must still respect its
        # deadline while it waits for pages.
        if self._resume:
            kept_resume = []
            for req in self._resume:
                if req.cancelled:
                    self._finish_cancelled(req, counted=False)
                    did = True
                elif self._expired(req, now):
                    self._finish_timeout(req, counted=False)
                    did = True
                else:
                    kept_resume.append(req)
            self._resume.clear()
            self._resume.extend(kept_resume)
        # Drain-and-refill: Queue supports no removal. Concurrent submits
        # during the refill just land behind the kept entries.
        kept: list[Request] = []
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if req.cancelled:
                self._finish_cancelled(req)
                did = True
            elif self._expired(req, now):
                self._finish_timeout(req)
                did = True
            else:
                kept.append(req)
        for req in kept:
            self._pending.put(req)
        return did

    def _finish_cancelled(self, req: Request, counted: bool = True) -> None:
        """Complete an unslotted cancelled request (``counted=False`` for
        preempted requests, which already left the admission count)."""
        with self._lock:
            self._requests.pop(req.id, None)
            if counted:
                self._pending_n -= 1
        self._observe_terminal(req, "cancelled")
        if req.emit:
            req.emit(-1, True)
        req.done.set()

    def _finish_timeout(self, req: Request, counted: bool = True) -> None:
        """Complete an unslotted request whose deadline already passed:
        in-band timeout terminal event, no slot consumed (``counted=False``
        for preempted requests — already out of the admission count)."""
        with self._lock:
            self._requests.pop(req.id, None)
            if counted:
                self._pending_n -= 1
        self._m_shed.inc(reason="timed_out")
        req.timed_out = True
        req.error = DeadlineExceeded(
            f"request {req.id} deadline exceeded while queued "
            f"({time.monotonic() - req.submitted_at:.2f}s in queue)"
        )
        self._observe_terminal(req, "timeout")
        if req.emit:
            req.emit(-1, True)
        req.done.set()

    def _pop_waiting(self) -> tuple[Request | None, bool, bool]:
        """(next live request, came-from-resume, swept-any-dead-entries).

        Preempted requests resume BEFORE anything in the pending queue;
        dead entries (cancelled, already expired) are completed on the spot
        so a burst of them never costs a free slot a step each."""
        swept = False
        while self._resume:
            req = self._resume.popleft()
            if req.cancelled:
                self._finish_cancelled(req, counted=False)
                swept = True
            elif self._expired(req):
                self._finish_timeout(req, counted=False)
                swept = True
            else:
                return req, True, swept
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return None, False, swept
            if req.cancelled:
                self._finish_cancelled(req)
                swept = True
            elif self._expired(req):
                self._finish_timeout(req)
                swept = True
            else:
                return req, False, swept

    def _shed_kv_exhausted(self, req: Request, cause: Exception) -> None:
        """Terminal shed for a request the allocator can never serve right
        now (pool dry with nothing in flight to free it — including the
        injected ``kv.alloc`` fault): RejectedError with Retry-After rides
        req.error so HTTP front-ends answer 429, and the emit channel gets
        its terminal event so nobody hangs."""
        self._m_shed.inc(reason="kv_exhausted")
        req.error = RejectedError(
            f"KV page pool exhausted: {cause}",
            retry_after_s=self.retry_after_s,
        )
        with self._lock:
            self._requests.pop(req.id, None)
        self._observe_terminal(req, "shed")
        if req.emit:
            try:
                req.emit(-1, True)
            except Exception:  # noqa: BLE001 — a bad sink must not kill the driver
                pass
        req.done.set()

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _active_requests(self) -> list[tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self._slot_req) if r is not None]

    def step(self) -> bool:
        """One scheduler iteration, pipelined for link latency:

          1. dispatch prefill+insert for every free slot with a waiting
             request (device work queued, nothing fetched yet);
          2. dispatch the next decode chunk for the active slots: 4 steps
             while a slot is free (whoever arrives meanwhile is seated when
             it ends, and step 3's tokens wait behind it on the device),
             decode_chunk steps once every slot is seated (_chunk_size);
          3. fetch + emit the prefills' first tokens (overlaps 2's compute);
          4. fetch + emit the PREVIOUS chunk's tokens (double buffering —
             the block for the chunk dispatched in 2 lands next step).

        Returns True if any work was done.
        """
        with self.spans.span("engine.step"):
            return self._step()

    def _step(self) -> bool:
        self._ensure_loaded()
        # Flight-recorder baselines: sync_stats / timer deltas over this
        # step become the step record's transfer counts and per-program
        # wall times (driver thread only — plain reads, no lock).
        step_t0 = time.monotonic()
        fetches0 = self.sync_stats["fetches"]
        uploads0 = self.sync_stats["uploads"]
        busy0 = self.timers.busy_seconds()
        self._step_tokens = 0
        self._step_preempts = 0
        did_work = self._sweep_cancelled()
        prefills = []
        exports = []
        free = list(self._free_slots())
        self._decoding = self.num_slots - len(free)
        with self.spans.span("engine.admit", free=len(free),
                             queued=self._pending_n + len(self._resume)):
            while free:
                req, resumed, swept = self._pop_waiting()
                did_work = did_work or swept
                if req is None:
                    break
                if not resumed:
                    with self._lock:
                        self._pending_n -= 1   # leaving the queue for a slot
                    self._m_queue_wait.observe(
                        time.monotonic() - req.submitted_at)
                if req.trace is not None:
                    req.trace.event("admitted")
                if req.export:
                    # Prefill-only (KV handoff export): no slot, no pages —
                    # the loop's free list is untouched, so a prefill cell
                    # drains export bursts without decode-slot contention.
                    try:
                        with self._prefill_span(req, -1) as span:
                            exports.append(
                                self._dispatch_prefill_export(req, span))
                    except Exception as e:
                        self._fail_request(req, e)
                        raise
                    did_work = True
                    continue
                slot = free.pop(0)
                try:
                    got = self._dispatch_prefill(req, slot)
                    # Import seats emit host-side (the first token came with
                    # the block) and return None — nothing to fetch later.
                    if got is not None:
                        prefills.append(got)
                except PagePoolExhausted as e:
                    # No pages for this prompt right now. If anything is in
                    # flight, pages WILL free (requests finish, preemption,
                    # prefix eviction) — park the request at the FRONT so it
                    # retries next step ahead of everyone. If the engine is
                    # otherwise idle, nothing will ever free pages: shed with
                    # RejectedError + Retry-After rather than deadlocking.
                    req.requeued = True
                    if (self._active_requests() or prefills
                            or self._inflight is not None):
                        self._resume.appendleft(req)
                    else:
                        self._shed_kv_exhausted(req, e)
                    did_work = True
                    break
                except Exception as e:
                    # The request is out of the queue but not yet slotted: fail
                    # it HERE or nobody ever wakes its waiter (_fail_all only
                    # sees slots and the queue).
                    self._fail_request(req, e)
                    raise
                did_work = True

        new_inflight = None
        try:
            if self._active_requests():
                with self.spans.span("engine.decode_dispatch") as span:
                    new_inflight = self._dispatch_decode_chunk(span)
                did_work = True

            if prefills:
                # One stacked fetch for every prefill's first token
                # (per-request int() would pay one link round-trip each);
                # the decode chunk dispatched above is already running
                # behind it on the device.
                with self.spans.span("engine.fetch_first", n=len(prefills)), \
                        jax.set_mesh(self.mesh):
                    firsts = self._fetch(jnp.stack([f for _, f in prefills]))
                if self._counted:
                    # a row: [token, *device-summed counters]
                    self._count_device_sums(firsts[:, 1:].sum(axis=0))
                    firsts = firsts[:, 0]
                with self.spans.span("engine.emit", tokens=len(prefills)):
                    for (req, _), first in zip(prefills, firsts):
                        self._emit(req, int(first))
        except Exception as e:
            # Dispatched-but-unfetched exports hold no slot and sit in no
            # queue, so _fail_all cannot find them — fail them HERE or
            # their waiters hang when this exception unwinds the step.
            for exp in exports:
                self._fail_request(exp[0], e)
            raise

        for exp in exports:
            # Export readbacks happen after the decode dispatch for the
            # same reason as the prefill fetch above: the host-bounce DMA
            # overlaps the chunk already running on the device.
            self._finish_export(*exp)
            did_work = True

        if self._inflight is not None:
            self._flush_inflight()
            did_work = True
        self._inflight = new_inflight
        if did_work:
            self._m_steps.inc()
            self._record_step(step_t0, fetches0, uploads0, busy0,
                              len(prefills), new_inflight)
            # Heartbeat writes stay under the admission lock everywhere
            # (kukelint KUKE005): submit() already updates it locked, and a
            # torn read on stalled_s()'s watchdog path is not worth the
            # nanoseconds an uncontended acquire costs per step.
            with self._lock:
                self.last_progress = time.monotonic()
        return did_work

    def _record_step(self, step_t0: float, fetches0: int, uploads0: int,
                     busy0: dict, prefills: int, inflight) -> None:
        """One flight-recorder record for a step that did work: occupancy,
        chunk size, tokens, transfer deltas, per-program wall-time deltas,
        preemptions, and the trace ids of everything seated — the
        postmortem `kuke timeline` reconstructs from. Driver thread only;
        the recorder's own short lock is the only synchronization."""
        seated = self._active_requests()
        programs = {}
        for name, busy in self.timers.busy_seconds().items():
            dt = busy - busy0.get(name, 0.0)
            if dt > 0.0:
                programs[name] = round(dt, 6)
        wall_s = time.monotonic() - step_t0
        self.recorder.record({
            "wall_s": round(wall_s, 6),
            "host_s": self.spans.host_s(wall_s),
            "occupancy": len(seated),
            "slots": self.num_slots,
            "queue_depth": self._pending_n + len(self._resume),
            "prefills": prefills,
            "chunk_k": inflight.k if inflight is not None else 0,
            "tokens": self._step_tokens,
            "fetches": self.sync_stats["fetches"] - fetches0,
            "uploads": self.sync_stats["uploads"] - uploads0,
            "preemptions": self._step_preempts,
            "programs": programs,
            "traces": [req.trace.trace_id for _slot, req in seated
                       if req.trace is not None],
        })

    def _prefix_lookup(self, req: Request) -> "_CachedPrefix | None":
        """Stored prefix usable for this request: its tokens must be a
        strict prefix of the prompt (equal would leave nothing to prefill,
        and the stored block carries no logits)."""
        if req.prefix_id is None:
            return None
        e = self._prefix_cache.get(req.prefix_id)
        if (
            e is not None
            and req.prompt.size > e.length
            and np.array_equal(req.prompt[: e.length], e.tokens)
        ):
            self._prefix_cache.move_to_end(req.prefix_id)
            return e
        return None

    def _prefix_store(self, prefix_id: str, prompt: np.ndarray,
                      kv_k, kv_v) -> None:
        if self._prefix_cache_size == 0 or self._prefix_cache_bytes == 0:
            return
        self._prefix_cache[prefix_id] = _CachedPrefix(
            tokens=prompt.copy(),
            kv_k=kv_k, kv_v=kv_v, length=int(prompt.size),
        )
        self._prefix_cache.move_to_end(prefix_id)
        # Evict LRU-first past either bound. An entry that alone exceeds the
        # byte budget evicts itself immediately — caching it would pin more
        # HBM than the operator allowed.
        while self._prefix_cache and (
            len(self._prefix_cache) > self._prefix_cache_size
            or sum(e.nbytes for e in self._prefix_cache.values())
            > self._prefix_cache_bytes
        ):
            self._prefix_cache.popitem(last=False)

    # --- paged prefix cache (shared refcounted pages, no tensor copies) ----

    def _prefix_shared_pages(self) -> float:
        """Distinct pool pages pinned by prefix entries (the scrape-time
        kukeon_kv_prefix_shared_pages gauge)."""
        if not self.paged:
            return 0.0
        pages: set[int] = set()
        for e in self._prefix_cache.values():
            pages.update(e.pages)
        return float(len(pages))

    def _prefix_lookup_paged(self, req: Request,
                             seq: np.ndarray) -> "SharedPrefix | None":
        """Usable stored prefix for ``seq``: its (page-aligned) tokens must
        be a strict prefix — equal would leave nothing to prefill."""
        if req.prefix_id is None:
            return None
        e = self._prefix_cache.get(req.prefix_id)
        if (
            e is not None
            and e.length > 0
            and seq.size > e.length
            and np.array_equal(seq[: e.length], e.tokens)
        ):
            self._prefix_cache.move_to_end(req.prefix_id)
            return e
        return None

    def _prefix_store_paged(self, prefix_id: str, seq: np.ndarray,
                            pages: list[int]) -> None:
        """(Re)point ``prefix_id`` at the slot's prompt pages — a refcount
        bump, not a copy. Only FULL pages are shared: the trailing partial
        page is about to receive the slot's decode writes, and sharing it
        would let one session corrupt another's KV."""
        if self._prefix_cache_size == 0 or self._prefix_cache_bytes == 0:
            return
        full = int(seq.size) // self.page_tokens
        if full == 0:
            return
        entry_pages = list(pages[:full])
        self._pool.ref(entry_pages)
        old = self._prefix_cache.pop(prefix_id, None)
        if old is not None:
            self._pool.unref(old.pages)
        self._prefix_cache[prefix_id] = SharedPrefix(
            tokens=np.asarray(seq[: full * self.page_tokens]).copy(),
            pages=entry_pages,
            length=full * self.page_tokens,
        )
        while self._prefix_cache and (
            len(self._prefix_cache) > self._prefix_cache_size
            or sum(e.nbytes(self._page_bytes)
                   for e in self._prefix_cache.values())
            > self._prefix_cache_bytes
        ):
            _k, e = self._prefix_cache.popitem(last=False)
            self._pool.unref(e.pages)

    def _reclaim_prefix_pages(self, need: int) -> bool:
        """Evict prefix entries LRU-first until ``need`` pages are free (or
        nothing evictable remains); True when the pages materialized. Only
        entries whose pages the cache alone holds are evicted: an entry
        pinned by a live slot would free ZERO pages now (the slot's
        references keep them resident) while losing the shared prefix for
        every admission behind it — strictly worse than leaving it be."""
        while self._pool.free < need and self._prefix_cache:
            victim = None
            for key, e in self._prefix_cache.items():       # LRU order
                if all(self._pool.refcount(p) == 1 for p in e.pages):
                    victim = key
                    break
            if victim is None:
                break
            e = self._prefix_cache.pop(victim)
            self._pool.unref(e.pages)
        return self._pool.free >= need

    def _rows_by_kind(self, lengths) -> dict[str, int]:
        """``<kind>_rows``: the rows slots of these token counts hold in a
        layer of each kind, and ``<kind>_slots`` for a kind that holds state
        without rows: the slots whose state the dispatch touches (a layered
        family's spans carry them; the dense path's one kind is
        ``live_rows`` already)."""
        if not self._layered:
            return {}
        return {f"{kd.name}_{kd.unit}": sum(kd.live(n) for n in lengths)
                for kd in self._kinds}

    def _decode_kv_rows(self, lengths) -> tuple[int, int]:
        """(held, read): the cache rows of all layers that a decode step
        over active slots of these token counts has before it, and those
        its attention fetches: the kernel's whole blocks up to each slot's
        last live row, or every held row where the XLA body runs."""
        held = read = 0
        for kd, block in zip(self._kinds, self._kv_blocks):
            all_rows = len(kd.layers) * self.num_slots * kd.rows
            held += all_rows
            if kd.arrays:   # rows of named arrays: what the kind says it reads
                read += len(kd.layers) * sum(kd.read(n) for n in lengths)
            else:
                read += all_rows if block is None else len(kd.layers) * sum(
                    -(-kd.live(n) // block) * block for n in lengths)
        return held, read

    def _prefill_span(self, req: Request, slot: int):
        """The span of one request's prefill dispatch (slot -1: an export,
        which takes none); ``_prefill_dispatched`` gives it its counts."""
        return self.spans.span("engine.prefill_dispatch", slot=slot,
                               request=_request_tag(req),
                               decoding=self._decoding)

    def _prefill_dispatched(self, req: Request, span, cached: int,
                            real: int, padded: int) -> None:
        """What every prefill dispatch records once it is enqueued: the
        tokens it ran through the model (``real``, the tail on a prefix
        hit), the bucket they ran in (``padded``) and the rows it took from
        the prefix store (``cached``), on its span and on the counters."""
        self.timers.note_tokens("prefill", padded)
        for kind, n in (("real", real), ("padded", padded),
                        ("cached", cached)):
            self._m_prefill_tokens.inc(n, kind=kind)
        span.set(program="prefill_ext" if cached else "prefill",
                 hit=int(cached > 0), cached=cached, real=real,
                 padded=padded, **self._rows_by_kind([cached + real]))
        if req.trace is not None:
            req.trace.event("prefill_dispatched")

    def _dispatch_prefill_paged(self, req: Request, slot: int, span):
        """Paged admission: allocate the prompt's pages, prefill (suffix-
        only over gathered shared pages on a prefix hit), scatter the block
        into the pool by page index, and activate the slot.

        A preempted request re-enters here with ``prompt + generated`` as
        its sequence — its KV was reclaimed, so the whole context re-
        prefills and generation continues where it stopped."""
        faults.maybe_fail("engine.prefill")
        seq = (req.prompt if not req.generated else
               np.concatenate([req.prompt,
                               np.asarray(req.generated, np.int32)]))
        n = int(seq.size)
        pt = self.page_tokens
        sp = req.sampling
        cached = self._prefix_lookup_paged(req, seq)
        shared = list(cached.pages) if cached is not None else []
        plen = cached.length if cached is not None else 0
        n_total = n // pt + 1            # pages covering positions [0, n]
        n_priv = n_total - len(shared)
        try:
            priv = self._pool.alloc(n_priv)
        except PagePoolExhausted:
            if not self._reclaim_prefix_pages(n_priv):
                raise
            # Eviction may have taken the entry we planned to share from;
            # the refcounts we hold nothing of yet make a clean retry.
            cached = self._prefix_lookup_paged(req, seq)
            shared = list(cached.pages) if cached is not None else []
            plen = cached.length if cached is not None else 0
            n_priv = n_total - len(shared)
            priv = self._pool.alloc(n_priv)
        self._pool.ref(shared)           # the slot now also holds them
        pages = shared + priv
        with jax.set_mesh(self.mesh):
            self._key, k1 = jax.random.split(self._key)
            if cached is not None:
                self.prefix_hits += 1
                # Gather the shared pages into the canonical prefix-bucket
                # block the extension prefill speaks (scratch-padded ids
                # keep one compile per bucket).
                Pb = min(self._bucket(plen), self.max_seq_len)
                gid = np.full((Pb // pt,), SCRATCH_PAGE, np.int32)
                gid[: len(shared)] = shared
                kv_k, kv_v = self._gather_block(
                    self.state.cache.k, self.state.cache.v,
                    self.state.cache.k_scale, self.state.cache.v_scale,
                    self._upload(gid),
                )
                tail = seq[plen:]
                bucket = min(self._bucket(tail.size), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, : tail.size] = tail
                first, out_k, out_v = self._prefill_ext(
                    self.params, kv_k, kv_v, plen,
                    self._upload(tokens), tail.size, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            else:
                if req.prefix_id is not None:
                    self.prefix_misses += 1
                bucket = min(self._bucket(n), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = seq
                first, out_k, out_v = self._prefill(
                    self.params, self._upload(tokens), n, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            # Scatter destinations for the block's pages: shared prefix
            # pages and bucket padding redirect to scratch (shared pages
            # are read-only; padding goes nowhere), private prompt pages
            # land in their pool slots.
            out_s = int(out_k.shape[2])
            ids = np.full((out_s // pt,), SCRATCH_PAGE, np.int32)
            prompt_pages = -(-n // pt)   # ceil: pages holding prompt rows
            for i in range(len(shared), prompt_pages):
                ids[i] = pages[i]
            self.state = self._insert_paged(
                self.state, out_k, out_v, n, self._upload(ids), slot, first)
        self._slot_pages[slot] = pages
        self._bt[slot, :] = SCRATCH_PAGE
        self._bt[slot, : len(pages)] = pages
        self._bt_dirty = True
        self._slot_disp[slot] = n
        if req.prefix_id is not None and cached is None:
            # Store only on a miss: a hit entry is already serving this
            # prefix_id, and re-pointing it at THIS session's page-aligned
            # prompt would fold the session's private tail into the entry —
            # poisoning the lookup for every sibling session whose prompt
            # diverges after the genuinely shared part.
            self._prefix_store_paged(req.prefix_id, seq, pages)
        req.slot = slot
        self._prefill_dispatched(req, span, plen, n - plen, bucket)
        self._slot_req[slot] = req
        self._slot_len[slot] = n + 1
        self._sampling_dirty = True
        return req, first

    def _dispatch_prefill(self, req: Request, slot: int):
        """Queue prefill+insert on device; returns (req, first-token device
        value) to fetch after other dispatches.

        With a prefix-cache hit, only the prompt's new suffix runs through
        the model (an agent session's shared context prefills once); the
        resulting prompt KV is (re)stored under the request's prefix_id
        either way."""
        if req.kv_import is not None and not req.generated:
            # KV handoff import: the prompt's KV arrived from a prefill
            # cell — seat it directly, never re-run prefill. A preempted
            # import re-enters with ``generated`` non-empty and takes the
            # normal re-prefill path below (its imported block is stale by
            # then; local prefill of prompt+generated rebuilds it).
            return self._dispatch_import(req, slot)
        with self._prefill_span(req, slot) as span:
            if self.paged:
                return self._dispatch_prefill_paged(req, slot, span)
            return self._dispatch_prefill_dense(req, slot, span)

    def _dispatch_prefill_dense(self, req: Request, slot: int, span):
        """The contiguous-KV prefill + insert behind ``_dispatch_prefill``."""
        faults.maybe_fail("engine.prefill")
        n = int(req.prompt.size)
        sp = req.sampling
        cached = self._prefix_lookup(req)
        with jax.set_mesh(self.mesh):
            self._key, k1 = jax.random.split(self._key)
            if cached is not None:
                self.prefix_hits += 1
                tail = req.prompt[cached.length:]
                bucket = min(self._bucket(tail.size), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, : tail.size] = tail
                first, *block = self._prefill_ext(
                    self.params, cached.kv_k, cached.kv_v, cached.length,
                    self._upload(tokens), tail.size, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            else:
                if req.prefix_id is not None:
                    self.prefix_misses += 1
                bucket = min(self._bucket(n), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = req.prompt
                # what the prompt leaves behind: kv_k, kv_v; a layered
                # family's block by its leaves
                first, *block = self._prefill(
                    self.params, self._upload(tokens), n, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            if req.prefix_id is not None and not self._layered:
                self._prefix_store(req.prefix_id, req.prompt, *block)
            self.state = self._insert(self.state, *block, n, slot, first)
        req.slot = slot
        plen = cached.length if cached is not None else 0
        self._prefill_dispatched(req, span, plen, n - plen, bucket)
        self._slot_req[slot] = req
        self._slot_len[slot] = n + 1   # prompt + the first generated token's kv-to-be
        self._sampling_dirty = True
        return req, first

    # --- disaggregated serving: KV handoff export / import -----------------

    def _dispatch_prefill_export(self, req: Request, span):
        """Prefill-only dispatch for a KV handoff export (disaggregated
        serving): run the prefill program, never seat a slot or touch the
        page pool — the caller fetches the dense KV block to host in
        :meth:`_finish_export`. Works in both layouts (the cold prefill
        program exists regardless of paging); on a legacy engine the
        prefix cache still participates, so N agent sessions exporting one
        shared context prefill only its suffix."""
        faults.maybe_fail("engine.prefill")
        n = int(req.prompt.size)
        sp = req.sampling
        cached = None if self.paged else self._prefix_lookup(req)
        with jax.set_mesh(self.mesh):
            self._key, k1 = jax.random.split(self._key)
            if cached is not None:
                self.prefix_hits += 1
                tail = req.prompt[cached.length:]
                bucket = min(self._bucket(tail.size), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, : tail.size] = tail
                first, kv_k, kv_v = self._prefill_ext(
                    self.params, cached.kv_k, cached.kv_v, cached.length,
                    self._upload(tokens), tail.size, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            else:
                if req.prefix_id is not None and not self.paged:
                    self.prefix_misses += 1
                bucket = min(self._bucket(n), self.max_seq_len)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = req.prompt
                first, kv_k, kv_v = self._prefill(
                    self.params, self._upload(tokens), n, k1,
                    jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                    jnp.float32(sp.top_p),
                )
            if req.prefix_id is not None and not self.paged:
                self._prefix_store(req.prefix_id, req.prompt, kv_k, kv_v)
        plen = cached.length if cached is not None else 0
        self._prefill_dispatched(req, span, plen, n - plen, bucket)
        return req, first, kv_k, kv_v, n

    def _finish_export(self, req: Request, first_dev, kv_k, kv_v, n: int):
        """Fetch an export's first token + prompt KV rows to host — both
        through the counted ``_fetch`` seam, so the handoff's transfer cost
        is visible in ``sync_stats`` and on /metrics — and complete the
        request with the payload the serving cell serializes over
        ``/v1/kv/export``."""
        try:
            with self.spans.span("engine.fetch_first", n=1), \
                    jax.set_mesh(self.mesh):
                first = int(self._fetch(first_dev))
                k_host = self._fetch(kv_k[:, :, :n])
                v_host = self._fetch(kv_v[:, :, :n])
        except Exception as e:  # noqa: BLE001 — fail THIS request, keep serving
            self._fail_request(req, e)
            return
        req.export_payload = {
            "token": first, "length": n, "k": k_host, "v": v_host,
            "pageTokens": self.page_tokens,
        }
        if req.trace is not None:
            req.trace.event("kv_exported",
                            bytes=int(k_host.nbytes + v_host.nbytes))
        with self._lock:
            self._requests.pop(req.id, None)
        self._observe_terminal(req, "ok")
        if req.emit:
            try:
                req.emit(first, True)
            except Exception:  # noqa: BLE001 — a bad sink must not kill the driver
                pass
        req.done.set()

    def _dispatch_import(self, req: Request, slot: int):
        """Seat a KV-handoff import directly into a decode slot: upload the
        prefill cell's block through the counted ``_upload`` seam, scatter
        it home with the existing ``insert_paged`` program (page-granular
        alloc, scratch-padded ids — one compile per bucket, shared with the
        local prefill path) or ``insert`` on the legacy layout, then emit
        the imported first token through the normal machinery. Prefill
        never re-runs here — that is the point of the handoff.

        ``PagePoolExhausted`` propagates to step()'s admission handler, so
        an import under pool pressure parks for resume (or sheds 429 when
        idle) exactly like a local prefill."""
        faults.maybe_fail("engine.prefill")
        imp = req.kv_import
        n = int(imp["length"])
        first = int(imp["token"])
        k_np, v_np = imp["k"], imp["v"]
        bucket = min(self._bucket(n), self.max_seq_len)
        want = np.dtype(self.cfg.dtype)

        def to_bucket(block):
            """Pad/trim the exporter's [L, 1, n, KV, D] rows to THIS
            engine's bucket shape and cache dtype (the two cells may run
            different bucket ladders or dtypes)."""
            out = block
            if out.dtype != want:
                out = out.astype(want)
            if out.shape[2] != bucket:
                padded = np.zeros(
                    (out.shape[0], 1, bucket) + out.shape[3:], dtype=want)
                rows = min(n, bucket)
                padded[:, :, :rows] = out[:, :, :rows]
                out = padded
            return out

        if self.paged:
            pt = self.page_tokens
            n_total = n // pt + 1      # pages covering positions [0, n]
            try:
                pages = self._pool.alloc(n_total)
            except PagePoolExhausted:
                if not self._reclaim_prefix_pages(n_total):
                    raise
                pages = self._pool.alloc(n_total)
            with jax.set_mesh(self.mesh):
                ids = np.full((bucket // pt,), SCRATCH_PAGE, np.int32)
                prompt_pages = -(-n // pt)   # ceil: pages holding KV rows
                ids[:prompt_pages] = pages[:prompt_pages]
                self.state = self._insert_paged(
                    self.state, self._upload(to_bucket(k_np)),
                    self._upload(to_bucket(v_np)), n,
                    self._upload(ids), slot, jnp.int32(first))
            self._slot_pages[slot] = pages
            self._bt[slot, :] = SCRATCH_PAGE
            self._bt[slot, : len(pages)] = pages
            self._bt_dirty = True
            self._slot_disp[slot] = n
        else:
            with jax.set_mesh(self.mesh):
                self.state = self._insert(
                    self.state, self._upload(to_bucket(k_np)),
                    self._upload(to_bucket(v_np)), n, slot,
                    jnp.int32(first))
        req.slot = slot
        self._slot_req[slot] = req
        self._slot_len[slot] = n + 1
        self._sampling_dirty = True
        if req.trace is not None:
            req.trace.event("kv_imported",
                            bytes=int(k_np.nbytes + v_np.nbytes),
                            pages=(len(self._slot_pages[slot])
                                   if self.paged else 0))
        # The imported first token flows through the normal emit machinery:
        # TTFT on this engine measures submit -> seated (the import cost),
        # and the finished checks (eos / stop tokens / max_new_tokens /
        # context cap) behave exactly as if this engine had produced the
        # token itself — including an immediate release when it is
        # terminal.
        self._emit(req, first)
        return None

    def _chunk_size(self) -> int:
        """Steps of the next decode chunk: decode_chunk with every slot
        seated, 4 while one is free, never past the cache's capacity.

        A request's max_new_tokens budget deliberately does NOT bound K:
        overshooting a finishing request wastes a few decode steps but keeps
        steady state on one compiled program (the freed slot's cache is reset
        by the next insert, so the overshoot KV is never observed).
        """
        k = self.decode_chunk
        # While a slot is free a request that arrives during this chunk can
        # be seated at its end, and a prefill dispatched this step gets its
        # first token after it: keep the chunk short. The queue is no sign
        # of that (step() has just emptied it into every free slot). With
        # every slot seated nobody can be admitted until someone finishes,
        # and short chunks would only multiply the per-chunk cost (dispatch,
        # whole-weight copies, the paged layout's gather/scatter).
        if self._free_slots():
            k = min(k, 4)
        # Capacity must count the un-flushed inflight chunk: the device cache
        # is already k_inflight steps ahead of the host's _slot_len.
        inflight_k = self._inflight.k if self._inflight is not None else 0
        for slot, _req in self._active_requests():
            k = min(k, self.max_seq_len - self._slot_len[slot] - inflight_k)
        k = max(1, k)
        # Round down to a power of 4 ({1, 4, 16, ...}) so the compile cache
        # stays tiny and warmup() can pre-compile every variant.
        size = 1
        while size * 4 <= k:
            size *= 4
        return size

    def _slot_sampling_arrays(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        return slot_sampling_arrays(self._active_requests(), self.num_slots)

    def _sampling_dev_arrays(self):
        """Device copies of the per-slot sampling arrays, re-uploaded only
        when the slot->request mapping changed since the last chunk."""
        if self._sampling_dev is None or self._sampling_dirty:
            temps, top_ks, top_ps = self._slot_sampling_arrays()
            self._sampling_dev = (
                self._upload(temps), self._upload(top_ks), self._upload(top_ps)
            )
            self._sampling_dirty = False
        return self._sampling_dev

    def _bt_dev_array(self):
        """Device copy of the block table, re-uploaded only when a slot's
        page list changed (insert/release/preempt/page growth) — the same
        dirty-flag discipline as the sampling arrays, so steady-state decode
        chunks perform no uploads at all."""
        if self._bt_dev is None or self._bt_dirty:
            self._bt_dev = self._upload(self._bt)
            self._bt_dirty = False
        return self._bt_dev

    def _preempt_victim(self, exclude: int) -> int | None:
        """Slot of the lowest-priority preemptable request: latest-submitted
        wins the axe (oldest requests keep their progress), never the slot
        we are allocating for."""
        victim, latest = None, -1.0
        for slot, req in self._active_requests():
            if slot == exclude or req.done.is_set():
                continue
            if req.submitted_at >= latest:
                victim, latest = slot, req.submitted_at
        return victim

    def _preempt_slot(self, slot: int, reason: str = "kv_pressure") -> None:
        """Pause an in-flight request and reclaim its pages: the request
        re-enters the queue AHEAD of new admissions and re-prefills
        prompt+generated when pages free. The inflight chunk was flushed by
        the caller, so every token already decoded for the victim has been
        emitted — nothing is lost but the KV, which re-prefill rebuilds."""
        req = self._slot_req[slot]
        if req is None or req.done.is_set():
            return
        self._m_preempt.inc(reason=reason)
        self._step_preempts += 1
        req.preemptions += 1
        req.requeued = True
        if req.trace is not None:
            req.trace.event("preempted")
        self._slot_req[slot] = None
        self._sampling_dirty = True
        self.state = DecodeState(
            cache=self.state.cache,
            tokens=self.state.tokens,
            active=self.state.active.at[slot].set(False),
        )
        self._pool.unref(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._slot_disp[slot] = 0
        self._slot_len[slot] = 0
        self._bt[slot, :] = SCRATCH_PAGE
        self._bt_dirty = True
        req.slot = -1
        self._resume.append(req)
        _LOG.debug("request %d preempted (%s), %d tokens so far",
                   req.id, reason, len(req.generated),
                   extra={"request_id": req.id, "phase": "preempted",
                          "trace_id": (req.trace.trace_id
                                       if req.trace is not None else None)})

    def _ensure_decode_pages(self, k: int) -> None:
        """Grow every active slot's block table to cover the next ``k``
        decode steps, reclaiming under pressure in escalating order: flush
        the inflight chunk (a finishing request frees its pages), evict
        prefix-cache entries LRU-first, preempt the lowest-priority other
        request, and — when one lone request simply cannot grow — finish it
        at its current length rather than wedging the engine."""
        for slot, req in self._active_requests():
            # Pressure handling for an earlier slot may have preempted or
            # finished this one mid-loop — skip anything no longer seated.
            if self._slot_req[slot] is not req or req.done.is_set():
                continue
            # Plan k steps ahead, but never past the request's own final
            # length (prompt + its max_new_tokens budget): rows an
            # overshooting chunk writes beyond the block table's last page
            # flat-map to scratch and are discarded with the overshoot
            # tokens, so allocating real pages for them would only
            # manufacture preemption pressure.
            limit = min(self.max_seq_len,
                        int(req.prompt.size) + req.sampling.max_new_tokens)
            need = min(
                self._pool.pages_for(min(self._slot_disp[slot] + k, limit)),
                self.max_pages_per_slot)
            while need > len(self._slot_pages[slot]):
                delta = need - len(self._slot_pages[slot])
                try:
                    got = self._pool.alloc(delta)
                except PagePoolExhausted:
                    if self._inflight is not None:
                        self._flush_inflight()
                        self._inflight = None
                        if req.done.is_set():
                            break       # the flush finished this request
                        continue        # retry: the flush may have freed pages
                    if self._reclaim_prefix_pages(delta):
                        continue
                    victim = self._preempt_victim(exclude=slot)
                    if victim is not None:
                        self._preempt_slot(victim)
                        continue
                    # Last resort: nobody else to reclaim from — finish
                    # this request at the tokens it already has.
                    self._release_slot(req, exhausted=True)
                    break
                base = len(self._slot_pages[slot])
                self._slot_pages[slot].extend(got)
                self._bt[slot, base: base + len(got)] = got
                self._bt_dirty = True

    def _dispatch_decode_chunk(self, span) -> "_InflightChunk | None":
        faults.maybe_fail("engine.decode")
        k = self._chunk_size()
        if self.paged:
            self._ensure_decode_pages(k)
            if not self._active_requests():
                return None      # pressure handling drained the batch
        active = self._active_requests()
        lens = [self._slot_len[slot] for slot, _req in active]
        span.set(k=k, active=len(active), live_rows=sum(lens),
                 **self._rows_by_kind(lens))
        held, read = self._decode_kv_rows(lens)
        self._m_kv_rows.inc(k * held, what="held")
        self._m_kv_rows.inc(k * read, what="read")
        if self._state_kinds:
            self._m_state_steps.inc(
                k * self._state_kinds * self.num_slots, what="held")
            self._m_state_steps.inc(
                k * self._state_kinds * len(active), what="active")
        temps_d, top_ks_d, top_ps_d = self._sampling_dev_arrays()
        with jax.set_mesh(self.mesh):
            self._key, k1 = jax.random.split(self._key)
            if self.paged:
                bt = self._bt_dev_array()
                self.state, toks = self._decode_chunk_paged(
                    self.params, self.state, bt, k1,
                    temps_d, top_ks_d, top_ps_d, k,
                )
                for slot, _req in self._active_requests():
                    self._slot_disp[slot] += k
            else:
                self.state, toks = self._decode_chunk(
                    self.params, self.state, k1,
                    temps_d, top_ks_d, top_ps_d, k,
                )
        self.sync_stats["chunks"] += 1
        self._m_chunks.inc(k=k)
        self.timers.note_tokens(
            "decode_chunk_paged" if self.paged else "decode_chunk",
            len(self._active_requests()) * k)
        for _slot, req in self._active_requests():
            if req.trace is not None:
                req.trace.decode_chunks += 1
        # Start the device→host DMA of the token block now: by the time
        # _flush_inflight wants it (after the NEXT chunk is dispatched), the
        # copy has overlapped device compute instead of serializing with it.
        toks.copy_to_host_async()
        return _InflightChunk(tokens=toks, k=k, slots=self._active_requests())

    def _flush_inflight(self):
        """Fetch + emit the previously dispatched chunk's token block."""
        chunk = self._inflight
        with self.spans.span("engine.fetch_chunk", k=chunk.k,
                             active=len(chunk.slots)):
            toks = self._fetch(chunk.tokens)  # [B, K] — single transfer per chunk
        if self._counted:   # rows past the slots: a counter each, by step
            self._count_device_sums(toks[self.num_slots:].sum(axis=1))
        with self.spans.span("engine.emit") as span:
            emitted0 = self._step_tokens
            for slot, req in chunk.slots:
                if req.done.is_set():
                    continue   # finished meanwhile (overshoot chunk) — discard
                base = self._slot_len[slot]
                for t in range(chunk.k):
                    # Per-token length bookkeeping so a request finishing
                    # mid-chunk keeps every token generated before the limit.
                    self._slot_len[slot] = base + t + 1
                    self._emit(req, int(toks[slot, t]))
                    if req.done.is_set():
                        break
                else:
                    self._slot_len[slot] = base + chunk.k
            span.set(tokens=self._step_tokens - emitted0)

    def _count_device_sums(self, sums) -> None:
        for counter, n in zip(self._counted, sums):
            counter.inc(int(n))

    def _emit(self, req: Request, token: int):
        now = time.monotonic()
        if not req.generated:
            # The instant the time to first token ends inside the program.
            with self.spans.span("engine.first_token",
                                 request=_request_tag(req)):
                req.first_token_at = now
                self._m_ttft.observe(
                    now - req.submitted_at,
                    exemplar=(req.trace.trace_id
                              if req.trace is not None else None))
                if req.trace is not None:
                    req.trace.event("first_token")
        elif req.last_token_at:
            self._m_itl.observe(now - req.last_token_at)
        req.last_token_at = now
        self._m_tokens.inc()
        self._step_tokens += 1
        req.generated.append(token)
        finished = (
            token in self.eos_ids
            or token in req.sampling.stop_tokens
            or len(req.generated) >= req.sampling.max_new_tokens
            or self._slot_len[req.slot] >= self.max_seq_len
        )
        if req.emit:
            req.emit(token, finished)
        if finished:
            self._release_slot(req)

    def _release_slot(self, req: Request, cancelled: bool = False,
                      timed_out: bool = False, exhausted: bool = False):
        slot = req.slot
        self._slot_req[slot] = None
        self._sampling_dirty = True
        self.state = DecodeState(
            cache=self.state.cache,
            tokens=self.state.tokens,
            active=self.state.active.at[slot].set(False),
        )
        if self.paged:
            # Page-granular free: the slot's references drop; pages still
            # pinned by a prefix entry (or a sibling session) stay resident,
            # everything else returns to the pool. Zeroing the block-table
            # row points any still-inflight decode write at scratch.
            self._pool.unref(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self._slot_disp[slot] = 0
            self._bt[slot, :] = SCRATCH_PAGE
            self._bt_dirty = True
        with self._lock:
            self._requests.pop(req.id, None)
        self._observe_terminal(
            req, "timeout" if timed_out else
            "cancelled" if cancelled else "ok")
        if (cancelled or timed_out or exhausted) and req.emit:
            # Streaming consumers need a terminal event on their channel;
            # cancellation/expiry (and a pool-exhausted early finish)
            # produces no token, so the sentinel is (-1, True) — a timeout
            # itself travels on req.timed_out.
            req.emit(-1, True)
        req.done.set()
