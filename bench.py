"""Benchmark: flagship 8B-class agent serving + Session cold-start.

BASELINE.json north star: 4 concurrent coding-agent sessions on a v5e-8
serving Llama-3-8B at >=1500 aggregate tok/s, p50 Session cold-start <90s.
This harness measures both, scaled to the chips actually present, and is
iso-model: on TPU the served model IS the 8B shape (int8 weights-only
quantization — ~8 GB — fits a single 16 GB v5e chip), so ``vs_baseline``
compares like with like (8B throughput vs the pro-rata 8B target,
1500 * n_chips / 8).

Pipeline (TPU):
  1. synthesize an 8B HF-hub-layout checkpoint (sharded safetensors +
     config.json + tokenizer.json) — no network egress, so weights are
     random at the real shapes; every serving byte still flows through the
     exact code a downloaded checkpoint would (models/checkpoints.py);
  2. stream-quantize it to the kukeon int8 format (cached);
  3. serve it through ServingEngine (continuous batching, chunked decode)
     with the checkpoint's real BPE tokenizer — measured in a subprocess so
     the orchestrator never holds the chip (libtpu is single-process);
  4. cold-start: 3x [fresh daemon -> `kuke apply` model-cell manifest ->
     first /v1/health 200], p50 (VERDICT r2/r3 item 2). The health endpoint
     answers only after weight load + compile warmup, so this is the full
     boot cost an agent session would see.

Prints exactly ONE JSON line:
  {"metric", "value" (tok/s), "unit", "vs_baseline", "trials",
   "cold_start": {"p50_s", "target_s", "runs_s"}}

Without a chip the bench exits non-zero. A tiny-model smoke of the same
two phases runs only when the caller asks for it with JAX_PLATFORMS=cpu,
and says backend=cpu in its line; a failed phase is a non-zero exit.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request
import uuid

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE = os.environ.get("KUKEON_BENCH_CACHE", "/tmp/kukeon-bench")
COLD_START_TARGET_S = 90.0


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _own_the_chip() -> None:
    """Start of every phase that builds an engine itself (the ServingCell
    phases get this from the cell): repo importable, and the same
    persistent compile cache the cells use, so the bench's children and
    chip_smoke.py's boots share one directory."""
    sys.path.insert(0, REPO)
    from kukeon_tpu.runtime.serving_cell import enable_compilation_cache

    enable_compilation_cache()


def subprocess_env() -> dict:
    """Env for child processes: the caller's, with the repo importable."""
    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if REPO not in parts:
        parts.insert(0, REPO)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env


def _require_backend(backend: str) -> str:
    """No fallback: anything but a TPU ends the bench with a non-zero exit
    unless the caller asked for the tiny CPU smoke with JAX_PLATFORMS=cpu —
    a CPU number must never stand in for a device number. Every phase
    passes the backend it found through here."""
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: no TPU (backend={backend}); the tiny CPU smoke runs "
            "only when asked for with JAX_PLATFORMS=cpu")
    return backend


def detect_backend() -> tuple[str, int]:
    """Backend + device count, probed in a throwaway subprocess so this
    orchestrator process never initializes (and then holds) the chip: a
    chip belongs to one process at a time, and every measuring child
    needs it.

    A backend that does not come up ends the bench with a non-zero exit,
    and so does anything but a TPU (:func:`_require_backend`)."""
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend(), len(jax.devices()))"],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env=subprocess_env(),
        )
    except subprocess.TimeoutExpired:
        raise SystemExit("bench: backend probe timed out (runtime "
                         "unreachable or wedged)") from None
    if out.returncode != 0:
        raise SystemExit(f"bench: no JAX backend came up:\n"
                         f"{out.stderr[-1500:]}")
    backend, n = out.stdout.split()[-2:]
    return _require_backend(backend), int(n)


# --- checkpoint prep (host-only, no TPU) -------------------------------------

def ensure_quantized_8b() -> str:
    """Synthesize the 8B HF checkpoint and its int8 quantized form (both
    cached under CACHE); returns the quantized checkpoint dir."""
    sys.path.insert(0, REPO)
    from kukeon_tpu.models import checkpoints, hf_convert, llama

    qdir = os.path.join(CACHE, "llama3-8b-int8")
    if checkpoints.is_quantized_checkpoint(qdir):
        return qdir
    hf_dir = os.path.join(CACHE, "llama3-8b-hf")
    cfg = llama.llama3_8b()
    t0 = time.monotonic()
    _log("synthesizing 8B HF checkpoint (one-time, ~16 GB)...")
    checkpoints.synthesize_hf_checkpoint(hf_dir, cfg)
    _log(f"synthesized in {time.monotonic() - t0:.0f}s; stream-quantizing to int8...")
    t0 = time.monotonic()
    params, cfg = hf_convert.load_params_quantized(hf_dir)
    checkpoints.save_quantized(qdir, params, cfg)
    # The serving cell wants the tokenizer next to the weights it loads.
    import shutil

    shutil.copy(os.path.join(hf_dir, "tokenizer.json"),
                os.path.join(qdir, "tokenizer.json"))
    _log(f"quantized in {time.monotonic() - t0:.0f}s -> {qdir}")
    return qdir


# --- serve phase (runs in its own process; owns the chip) ---------------------

def phase_serve(args) -> None:
    import numpy as np

    _own_the_chip()
    import jax

    from kukeon_tpu.models import checkpoints, llama
    from kukeon_tpu.parallel import auto_mesh_shape, make_mesh
    from kukeon_tpu.serving import SamplingParams, ServingEngine
    from kukeon_tpu.serving.tokenizer import load_tokenizer

    backend = _require_backend(jax.default_backend())
    n_chips = len(jax.devices())
    if args.chips:
        # Sharding-layout arm: exactly N chips, all on the tensor axis
        # (over-asking the host fails loudly in serving_mesh).
        from kukeon_tpu.parallel import serving_mesh

        mesh = serving_mesh(args.chips)
    else:
        shape = auto_mesh_shape(n_chips)
        mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])

    if args.checkpoint:
        params, cfg = checkpoints.load_quantized(args.checkpoint)
        tokenizer = load_tokenizer(args.checkpoint)
        model_id, model_name = "llama3-8b", "llama3-8b (int8)"
        sessions, prompt_len, new_tokens, max_seq = 4, 128, 128, 1024
    else:
        cfg = llama.llama_tiny()
        params = llama.init_params(jax.random.key(0), cfg)
        tokenizer = None
        model_id, model_name = "tiny", "tiny (cpu smoke)"
        sessions, prompt_len, new_tokens, max_seq = 2, 32, 16, 128

    buckets = None
    if args.prefill_buckets:
        buckets = tuple(int(b) for b in args.prefill_buckets.split(","))
    engine = ServingEngine(
        cfg, params, mesh, num_slots=sessions, max_seq_len=max_seq,
        decode_chunk=args.decode_chunk, kv_cache_int8=args.kv_int8,
        prefill_buckets=buckets, kv_page_tokens=args.kv_page_tokens or 0,
        # auto = the engine's divisibility default (then the tune profile);
        # on/off pin the KV-pool layout for a sharding-sweep arm.
        kv_shard={"auto": None, "on": True, "off": False}[args.kv_shard],
    )

    _LAT_HISTS = (("ttft", "kukeon_engine_ttft_seconds"),
                  ("inter_token", "kukeon_engine_inter_token_seconds"),
                  ("e2e", "kukeon_engine_e2e_seconds"))

    def latency_snapshot():
        return {name: engine.registry.get(name).snapshot()[0]
                for _s, name in _LAT_HISTS}

    def latency_percentiles(base):
        """p50/p95/p99 TTFT, inter-token, and e2e latency read from the
        engine's OWN obs histograms — the perf trajectory is measured by
        the product's instruments, not a harness-side stopwatch. Counts
        are deltas against the post-warmup snapshot so the warmup
        request's compile time never pollutes the percentiles."""
        from kukeon_tpu.obs import percentile_from_counts

        out = {}
        for short, name in _LAT_HISTS:
            h = engine.registry.get(name)
            counts = [c - b for c, b in zip(h.snapshot()[0], base[name])]
            ps = {f"p{int(q * 100)}": percentile_from_counts(
                h.buckets, counts, q) for q in (0.5, 0.95, 0.99)}
            if all(v is not None for v in ps.values()):
                out[short] = {k: round(v, 5) for k, v in ps.items()}
        return out

    rng = np.random.default_rng(0)
    if tokenizer is not None:
        # Real-tokenizer prompts: encode an agent-ish request, tile to the
        # measured prompt length.
        base = tokenizer.encode(
            "You are a coding agent. Read the build failure below and "
            "produce a minimal patch.\n\ndef main(argv):\n    return run(argv)\n"
        )
        prompts = []
        for i in range(sessions):
            ids = (base * (prompt_len // len(base) + 1))[:prompt_len]
            prompts.append(np.asarray(ids, np.int32))
    else:
        prompts = [
            rng.integers(1, cfg.vocab_size, size=prompt_len).astype(np.int32)
            for _ in range(sessions)
        ]
    sp = SamplingParams(max_new_tokens=new_tokens)

    # AOT precompile first: it feeds ProgramTimers the static
    # cost-analysis FLOPs/bytes (the denominators behind the per-program
    # MFU / membw gauges and the artifact's program_costs section) and
    # pre-warms the compile cache the warmup dispatch then hits.
    engine.precompile((prompt_len,))
    engine.warmup(prompt_len, sp)
    # Warmup's single pass overlaps the tail of the async param transfer;
    # measuring before every byte lands would charge transfer time to
    # trial 1 (r5: first trial measured 2 tok/s vs 261 steady-state).
    jax.block_until_ready(engine.params)
    _log("warmup done; measuring...")
    lat_base = latency_snapshot()

    # Median of several trials.
    trials = 1 if backend == "cpu" else 3
    rates = []
    for _ in range(trials):
        t0 = time.monotonic()
        reqs = [engine.submit(p, sp) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            engine.step()
        dt = time.monotonic() - t0
        total_tokens = sum(len(r.generated) for r in reqs)
        rates.append(total_tokens / dt)
    rates.sort()
    # Device-layer facts ride along with every serve measurement: compile
    # counts by program (an unexpected steady-state retrace shows up as a
    # moving decode count between artifacts) and peak HBM (headroom for
    # slot-count / context-length tuning). Both read from the engine's own
    # obs instruments; peak is None on backends without memory stats (CPU).
    compiles = {p: engine.compiles.count(p)
                for p in ("prefill", "insert", "decode")}
    # Roofline ride-along (v8): per-program dispatch counts, settled wall
    # time, token totals, and the static FLOPs/bytes precompile captured,
    # plus the headline MFU (the busiest program's model-FLOPs
    # utilization). All read from the engine's own ProgramTimers — the
    # same numbers /metrics exposes as kukeon_program_* gauges.
    engine.timers.settle()
    program_costs = engine.timers.snapshot()
    mfu = max((c.get("mfu") or 0.0) for c in program_costs.values()) \
        if program_costs else 0.0
    peak_hbm = None
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001
            ms = None
        if ms and "peak_bytes_in_use" in ms:
            peak_hbm = max(peak_hbm or 0, int(ms["peak_bytes_in_use"]))
    print(json.dumps({
        "backend": backend,
        "n_chips": n_chips,
        "model": model_name,
        "model_id": model_id,
        "sessions": sessions,
        "tok_per_s": rates[len(rates) // 2],
        "trials": [round(r, 1) for r in rates],
        "latency_s": latency_percentiles(lat_base),
        "compiles": compiles,
        "program_costs": program_costs,
        # Six digits, matching timers.snapshot(): a CPU-smoke MFU is
        # O(1e-5) and a 4-digit round would flatten it to a lying zero.
        "mfu": round(mfu, 6),
        "peak_hbm_bytes": peak_hbm,
        "kv_page_tokens": engine.page_tokens,
        # The mesh this measurement ran on: chips, the tensor-axis size,
        # and whether the KV pool actually sharded over it (the engine may
        # replicate on a head-divisibility miss even when asked to shard).
        "mesh": {
            "chips": int(mesh.size),
            "tensor": int(mesh.shape["tensor"]),
            "kv_sharded": bool(any(engine._cache_shardings()[0].spec)),
        },
        "config": {
            "decode_chunk": engine.decode_chunk,
            "kv_cache_int8": engine.kv_cache_int8,
            "prefill_buckets": (list(engine.prefill_buckets)
                                if buckets else None),
            "kv_page_tokens": engine.page_tokens,
            "chips": args.chips,
            "kv_shard": args.kv_shard,
        },
    }), flush=True)


def phase_mixed(args) -> None:
    """Agent-session workload on a FIXED KV HBM budget (the paged-KV
    acceptance bench): bimodal prompt/generation lengths, sessions reusing
    a shared prefix, submitted as one preemption-inducing flood. The same
    workload runs against the legacy contiguous engine and the paged
    engine at equal KV rows, and the line reports max concurrent sessions,
    aggregate tok/s, preemptions, and failures (which must be zero) for
    each arm — the paged engine's win is concurrency at equal HBM, not a
    faster single decode step."""
    import gc

    import numpy as np

    _own_the_chip()
    import jax

    from kukeon_tpu.models import checkpoints, llama
    from kukeon_tpu.parallel import auto_mesh_shape, make_mesh
    from kukeon_tpu.serving import SamplingParams, ServingEngine

    backend = _require_backend(jax.default_backend())
    n_chips = len(jax.devices())
    shape = auto_mesh_shape(n_chips)
    mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])

    if args.checkpoint:
        params, cfg = checkpoints.load_quantized(args.checkpoint)
        model_id = "llama3-8b"
        max_seq, legacy_slots, paged_slots = 1024, 4, 12
        pt = args.kv_page_tokens or 64
        prefix_len, chat_tail, long_tail = 256, 32, 384
        chat_gen, long_gen, n_sessions = 64, 128, 24
    else:
        cfg = llama.llama_tiny()
        params = llama.init_params(jax.random.key(0), cfg)
        model_id = "tiny"
        max_seq, legacy_slots, paged_slots = 128, 2, 4
        pt = args.kv_page_tokens or 16
        prefix_len, chat_tail, long_tail = 64, 8, 40
        chat_gen, long_gen, n_sessions = 32, 24, 24

    # Equal HBM: the paged pool holds exactly the KV rows the legacy
    # engine reserves up front (legacy_slots * max_seq), carved into
    # pages. The paged arm gets more decode slots — slots are scheduling
    # entries there, the pool is what bounds memory.
    kv_rows = legacy_slots * max_seq
    pool_pages = kv_rows // pt

    rng = np.random.default_rng(7)
    prefix = rng.integers(1, cfg.vocab_size, size=prefix_len).astype(np.int32)
    workload = []            # (prompt, max_new_tokens)
    for i in range(n_sessions):
        is_long = i % 2 == 1   # bimodal: half long agent turns, half chatty
        tail = rng.integers(
            1, cfg.vocab_size,
            size=long_tail if is_long else chat_tail).astype(np.int32)
        workload.append((np.concatenate([prefix, tail]),
                         long_gen if is_long else chat_gen))

    def run_arm(kv_page_tokens: int, num_slots: int) -> dict:
        engine = ServingEngine(
            cfg, params, mesh, num_slots=num_slots, max_seq_len=max_seq,
            decode_chunk=args.decode_chunk, kv_cache_int8=args.kv_int8,
            kv_page_tokens=kv_page_tokens,
            kv_pool_pages=pool_pages if kv_page_tokens else None,
        )
        engine.warmup(prefix_len + chat_tail)
        jax.block_until_ready(engine.params)
        # Warm the prefix path before measuring: the first shared-prefix
        # request stores the prefix, the second compiles the extension
        # prefill (gather + suffix-only programs) — steady-state agent
        # serving runs warm, and a compile inside the timed flood would
        # charge one-time cost to the throughput number.
        for p, gen in (workload[0], workload[1], workload[2]):
            r = engine.submit(p, SamplingParams(max_new_tokens=gen),
                              prefix_id="agent")
            while not r.done.is_set():
                engine.step()
        base_preempt = int(engine._m_preempt.value(reason="kv_pressure"))
        base_hits = engine.prefix_hits
        t0 = time.monotonic()
        reqs = [
            engine.submit(p, SamplingParams(max_new_tokens=gen),
                          prefix_id="agent")
            for p, gen in workload
        ]
        max_sessions = 0
        while not all(r.done.is_set() for r in reqs):
            engine.step()
            max_sessions = max(
                max_sessions,
                sum(1 for r in engine._slot_req if r is not None))
        dt = time.monotonic() - t0
        total = sum(len(r.generated) for r in reqs)
        out = {
            "max_sessions": max_sessions,
            "tok_per_s": round(total / dt, 2),
            "tokens": total,
            "wall_s": round(dt, 2),
            "failed": sum(1 for r in reqs if r.error is not None),
            "preemptions": int(engine._m_preempt.value(
                reason="kv_pressure")) - base_preempt,
            "prefix_hits": engine.prefix_hits - base_hits,
            "compiles": {p: engine.compiles.count(p)
                         for p in ("prefill", "insert", "decode")},
        }
        engine.stop()
        del engine
        gc.collect()
        return out

    _log(f"mixed: legacy arm ({legacy_slots} slots, {kv_rows} KV rows)...")
    legacy = run_arm(0, legacy_slots)
    _log(f"mixed legacy: {legacy}")
    _log(f"mixed: paged arm ({paged_slots} slots, {pool_pages} pages of "
         f"{pt})...")
    paged = run_arm(pt, paged_slots)
    _log(f"mixed paged: {paged}")

    line = {
        "metric": (f"mixed agent sessions, {model_id}, {n_sessions} "
                   f"bimodal requests, shared prefix, equal KV HBM "
                   f"({kv_rows} rows), {n_chips} chip(s) [{backend}]"),
        "backend": backend,
        "n_chips": n_chips,
        "model": model_id,
        "kv_page_tokens": pt,
        "kv_pool_pages": pool_pages,
        "arms": {"legacy": legacy, "paged": paged},
        "max_sessions_gain": (round(paged["max_sessions"]
                                    / max(1, legacy["max_sessions"]), 2)),
        "tok_per_s_gain": (round(paged["tok_per_s"]
                                 / max(1e-9, legacy["tok_per_s"]), 3)),
    }
    if args.out:
        serve = {
            "backend": backend, "n_chips": n_chips, "model": model_id,
            "model_id": model_id, "sessions": n_sessions,
            "tok_per_s": paged["tok_per_s"],
            "trials": [paged["tok_per_s"]],
            "kv_page_tokens": pt,
            "max_sessions": paged["max_sessions"],
            "compiles": paged["compiles"],
        }
        write_artifact(args.out, serve, {"mixed": line})
    print(json.dumps(line), flush=True)


def phase_disagg(args) -> None:
    """Disaggregated prefill/decode serving vs mixed co-location at equal
    chips and equal KV HBM (`bench.py --mixed --disagg`): the bimodal
    agent-session flood runs twice through the REAL gateway + HTTP path —
    once against two ``mixed`` replicas, once against a 1-prefill +
    1-decode split with the page-granular KV handoff between them. Both
    arms use identical cells (same slots, same page pool) so the only
    variable is the architecture.

    TTFT is measured CLIENT-side: wall time from POST to the first ndjson
    line of a streaming request — the exact latency the TTFT-p95 SLO
    tracker pages on. The disaggregated arm's first token goes out after
    prefill+transfer, before the request waits for a decode slot; the
    mixed arm's waits for slot seating behind co-located decode — that
    architectural difference is what this phase quantifies. The handoff
    cost itself rides along from the gateway's own
    ``kukeon_handoff_seconds`` histogram."""
    import threading
    from http.server import ThreadingHTTPServer

    import numpy as np

    sys.path.insert(0, REPO)
    import jax

    from kukeon_tpu.gateway.cell import GatewayCell, make_gateway_handler
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    backend = _require_backend(jax.default_backend())
    n_chips = len(jax.devices())
    # Tiny-model scale on every backend: the layer under test is the
    # serving architecture (routing, handoff, slot queueing), not the
    # matmuls — same rationale as the gateway phase.
    num_slots = 2
    max_seq = 128
    pt = args.kv_page_tokens or 16
    prefix_len, chat_tail, long_tail = 48, 8, 32
    chat_gen, long_gen, n_sessions = 12, 40, 16

    rng = np.random.default_rng(7)
    prefix = [int(x) for x in rng.integers(1, 250, size=prefix_len)]
    workload = []            # (promptTokens, max_new_tokens)
    for i in range(n_sessions):
        is_long = i % 2 == 1   # bimodal: half long agent turns, half chatty
        tail = [int(x) for x in rng.integers(
            1, 250, size=long_tail if is_long else chat_tail)]
        workload.append((prefix + tail,
                         long_gen if is_long else chat_gen))

    def run_arm(roles: tuple) -> dict:
        import http.client

        cells, servers, urls = [], [], []
        for role in roles:
            cell = ServingCell(
                "tiny", num_slots=num_slots, max_seq_len=max_seq,
                checkpoint=None, dtype=None, kv_page_tokens=pt,
                max_pending=512, role=role)
            cell.engine.start()
            cell.mark_ready()
            srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            cells.append(cell)
            servers.append(srv)
            urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
        gw = GatewayCell("tiny", urls, poll_interval_s=0.1)
        gw.start()
        gw.router.poll_once()
        gw_srv = ThreadingHTTPServer(("127.0.0.1", 0),
                                     make_gateway_handler(gw))
        threading.Thread(target=gw_srv.serve_forever, daemon=True).start()
        port = gw_srv.server_address[1]

        def post_stream(body: dict):
            """(ttft_s, n_tokens, status, saw_error) for one streaming
            request — TTFT stops at the FIRST ndjson line's arrival."""
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
            t0 = time.monotonic()
            conn.request("POST", "/v1/generate",
                         body=json.dumps({**body, "stream": True}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                conn.close()
                return None, 0, resp.status, True
            first = resp.readline()
            ttft = time.monotonic() - t0
            rest = resp.read()
            conn.close()
            toks = 0
            err = False
            for ln in (first + rest).splitlines():
                try:
                    rec = json.loads(ln)
                except ValueError:
                    err = True
                    continue
                if "token" in rec:
                    toks += 1
                if "error" in rec:
                    err = True
            return ttft, toks, 200, err

        # Warm the whole path untimed (compiles: both prefill buckets,
        # insert, decode chunks, the prefix-extension program, and — on
        # the disagg arm — the export/import seams), so the timed flood
        # measures architecture, not compilation.
        for prompt, gen in (workload[0], workload[1], workload[2]):
            post_stream({"promptTokens": prompt, "maxNewTokens": gen,
                         "prefixId": "agent"})

        ttfts: list = []
        totals = [0]
        failures = [0]
        lock = threading.Lock()
        t0 = time.monotonic()

        def session(i: int) -> None:
            prompt, gen = workload[i]
            ttft, toks, status, err = post_stream(
                {"promptTokens": prompt, "maxNewTokens": gen,
                 "prefixId": "agent"})
            with lock:
                if status != 200 or err:
                    failures[0] += 1
                if ttft is not None:
                    ttfts.append(ttft)
                totals[0] += toks

        threads = [threading.Thread(target=session, args=(i,))
                   for i in range(n_sessions)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.monotonic() - t0

        ttfts.sort()
        h = gw.registry.get("kukeon_handoff_seconds")
        handoff_p50 = h.percentile(0.5)
        out = {
            "roles": list(roles),
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4) if ttfts else None,
            "ttft_p95_s": (round(ttfts[min(len(ttfts) - 1,
                                           int(len(ttfts) * 0.95))], 4)
                           if ttfts else None),
            "tok_per_s": round(totals[0] / wall, 2),
            "tokens": totals[0],
            "wall_s": round(wall, 2),
            "failed": failures[0],
            "handoff_ms_p50": (round(handoff_p50 * 1000, 2)
                               if handoff_p50 is not None else None),
            "handoffs": int(sum(h.snapshot()[0])),
            "handoff_pages": int(gw.registry.get(
                "kukeon_handoff_pages_total").value()),
            "handoff_bytes": int(gw.registry.get(
                "kukeon_handoff_bytes_total").value()),
            "handoff_fallbacks": int(gw.registry.get(
                "kukeon_handoff_fallback_total").value()),
        }
        gw_srv.shutdown()
        gw.stop()
        for srv in servers:
            srv.shutdown()
        for cell in cells:
            cell.engine.stop()
        return out

    _log("disagg: mixed arm (2x mixed)...")
    mixed = run_arm(("mixed", "mixed"))
    _log(f"disagg mixed arm: {mixed}")
    _log("disagg: disaggregated arm (1 prefill + 1 decode)...")
    disagg = run_arm(("prefill", "decode"))
    _log(f"disagg arm: {disagg}")

    line = {
        "metric": (f"disaggregated vs mixed serving, tiny, {n_sessions} "
                   f"bimodal sessions, equal KV HBM, {n_chips} chip(s) "
                   f"[{backend}]"),
        "backend": backend,
        "n_chips": n_chips,
        "model": "tiny",
        "kv_page_tokens": pt,
        "arms": {"mixed": mixed, "disagg": disagg},
        "ttft_p95_gain": (round(mixed["ttft_p95_s"] / disagg["ttft_p95_s"], 3)
                          if mixed["ttft_p95_s"] and disagg["ttft_p95_s"]
                          else None),
        "tok_per_s_ratio": round(
            disagg["tok_per_s"] / max(1e-9, mixed["tok_per_s"]), 3),
        "handoff_ms_p50": disagg["handoff_ms_p50"],
    }
    if args.out:
        serve = {
            "backend": backend, "n_chips": n_chips, "model": "tiny",
            "model_id": "tiny", "sessions": n_sessions, "replicas": 2,
            "tok_per_s": disagg["tok_per_s"],
            "trials": [disagg["tok_per_s"]],
            "kv_page_tokens": pt,
            "ttft_p95_s": disagg["ttft_p95_s"],
        }
        write_artifact(args.out, serve, {
            "disagg": line, "handoff_ms_p50": disagg["handoff_ms_p50"]})
    print(json.dumps(line), flush=True)


def phase_gateway(args) -> None:
    """Scale-out serving through the replica gateway (`--replicas N`): N
    in-process serving cells behind a GatewayCell, flooded by concurrent
    prefix-id-carrying sessions. Measures aggregate tok/s THROUGH the proxy
    plus the retry/shed work the routing layer absorbed. The replicas run
    the tiny model on purpose — the layer under test is the gateway
    (routing, affinity, passthrough), not the matmuls, so the number is
    comparable on any backend."""
    import threading
    from http.server import ThreadingHTTPServer

    import numpy as np  # noqa: F401 — serving cell deps

    sys.path.insert(0, REPO)
    import jax

    from kukeon_tpu.gateway.cell import GatewayCell, make_gateway_handler
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    n = max(2, args.replicas)
    backend = _require_backend(jax.default_backend())
    _log(f"gateway: {n} tiny replicas [{backend}]")
    cells, servers, urls = [], [], []
    for _i in range(n):
        cell = ServingCell("tiny", num_slots=4, max_seq_len=128,
                           checkpoint=None, dtype=None, max_pending=256)
        cell.engine.start()
        cell.mark_ready()
        srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        cells.append(cell)
        servers.append(srv)
        urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
    gw = GatewayCell("tiny", urls, poll_interval_s=0.1)
    gw.start()
    gw_srv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=gw_srv.serve_forever, daemon=True).start()
    gw.router.poll_once()

    sessions = 2 * n
    per_session = 6
    new_tokens = 16
    tokens = [0]
    statuses: dict[int, int] = {}
    lock = threading.Lock()
    t0 = time.monotonic()

    def session(i: int) -> None:
        import http.client

        for _turn in range(per_session):
            conn = http.client.HTTPConnection(
                "127.0.0.1", gw_srv.server_address[1], timeout=120)
            conn.request("POST", "/v1/generate", body=json.dumps({
                "prompt": f"session {i} turn", "maxNewTokens": new_tokens,
                "prefixId": f"sess-{i}"}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            with lock:
                statuses[resp.status] = statuses.get(resp.status, 0) + 1
                if resp.status == 200:
                    tokens[0] += json.loads(body).get("numTokens", 0)

    threads = [threading.Thread(target=session, args=(i,))
               for i in range(sessions)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    dt = time.monotonic() - t0

    total = sum(statuses.values())
    retries = int(sum(v for _l, v in gw.registry.get(
        "kukeon_gateway_retries_total").samples()))
    result = {
        "metric": f"gateway aggregate tok/s, {n} replicas, "
                  f"{sessions} sessions, tiny [{backend}]",
        "backend": backend,
        "model": "tiny",
        "model_id": "tiny",
        "n_chips": len(jax.devices()),
        "replicas": n,
        "sessions": sessions,
        "tok_per_s": round(tokens[0] / dt, 2),
        "requests": total,
        "retry_rate": round(retries / max(total, 1), 4),
        "shed": int(gw.registry.get("kukeon_gateway_shed_total").value()),
        "statuses": {str(k): v for k, v in sorted(statuses.items())},
        "trials": [round(tokens[0] / dt, 1)],
    }
    gw_srv.shutdown()
    gw.stop()
    for srv in servers:
        srv.shutdown()
    for cell in cells:
        cell.engine.stop()
    if args.out:
        write_artifact(args.out, result, result)
    print(json.dumps(result), flush=True)


def phase_diurnal(args) -> None:
    """Diurnal traffic ramp through the replica gateway (`--diurnal`):
    tiny replicas behind a GatewayCell, driven by an open-loop arrival
    schedule that triples from night to peak and falls past the trough.
    The replicas are deliberately sized so the peak overruns their
    admission queues — the measurement is the gateway's SPILLOVER
    contract (an all-shed storm becomes client latency, never a
    client-visible 429) plus per-stage achieved throughput and client-
    side p95, the workload shape the FleetScaler's reconcile loop is
    built for (kukeon-bench/v5 `diurnal` section)."""
    import threading
    from http.server import ThreadingHTTPServer

    sys.path.insert(0, REPO)
    import jax

    from kukeon_tpu.gateway.cell import GatewayCell, make_gateway_handler
    from kukeon_tpu.runtime.serving_cell import ServingCell, make_handler

    n = max(2, args.replicas)
    backend = _require_backend(jax.default_backend())
    stage_s = float(os.environ.get("KUKEON_BENCH_DIURNAL_STAGE_S", "5"))
    _log(f"diurnal: {n} tiny replicas, {stage_s:.0f}s stages [{backend}]")
    cells, servers, urls = [], [], []
    for _i in range(n):
        # Small slots + shallow admission queue: the peak stage must be
        # able to shed, or the spillover path under test never runs.
        cell = ServingCell("tiny", num_slots=2, max_seq_len=128,
                           checkpoint=None, dtype=None, max_pending=4)
        cell.engine.start()
        cell.mark_ready()
        srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(cell))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        cells.append(cell)
        servers.append(srv)
        urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
    gw = GatewayCell("tiny", urls, poll_interval_s=0.1,
                     spill_max_wait_s=30.0)
    gw.start()
    gw_srv = ThreadingHTTPServer(("127.0.0.1", 0), make_gateway_handler(gw))
    threading.Thread(target=gw_srv.serve_forever, daemon=True).start()
    gw.router.poll_once()
    gport = gw_srv.server_address[1]

    stages = (("night", 4.0), ("peak", 12.0), ("trough", 2.0))   # req/s
    tokens = [0]
    lock = threading.Lock()
    t_run0 = time.monotonic()

    def one_request(i: int, rows: list) -> None:
        import http.client

        t0 = time.monotonic()
        status = None
        try:
            conn = http.client.HTTPConnection("127.0.0.1", gport,
                                              timeout=120)
            conn.request("POST", "/v1/generate", body=json.dumps({
                "prompt": f"turn {i}", "maxNewTokens": 8,
                "prefixId": f"sess-{i % 16}", "deadlineS": 60}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            status = resp.status
            if status == 200:
                with lock:
                    tokens[0] += json.loads(body).get("numTokens", 0)
        except Exception:  # noqa: BLE001 — a transport error is a data point
            status = -1
        with lock:
            rows.append((status, time.monotonic() - t0))

    stage_results = []
    for name, rate in stages:
        rows: list = []
        threads = []
        t_end = time.monotonic() + stage_s
        i = 0
        while time.monotonic() < t_end:
            th = threading.Thread(target=one_request, args=(i, rows))
            th.start()
            threads.append(th)
            i += 1
            time.sleep(1.0 / rate)
        for th in threads:
            th.join(timeout=300)
        lat = sorted(t for s, t in rows if s == 200)
        stage_results.append({
            "stage": name, "target_rps": rate, "requests": len(rows),
            "qps": round(len(rows) / stage_s, 2),
            "p95_s": (round(lat[int(0.95 * (len(lat) - 1))], 4)
                      if lat else None),
            "statuses": {str(k): sum(1 for s, _t in rows if s == k)
                         for k in sorted({s for s, _t in rows})},
        })
        _log(f"diurnal stage {name}: {json.dumps(stage_results[-1])}")
    dt = time.monotonic() - t_run0

    spill = {k: int(gw.registry.get("kukeon_gateway_spill_total").value(
        outcome=k)) for k in ("recovered", "timeout", "overflow", "fault")}
    total = sum(r["requests"] for r in stage_results)
    failed = sum(v for r in stage_results
                 for s, v in r["statuses"].items() if s != "200")
    diurnal = {
        "stages": stage_results,
        "spill": spill,
        "peak_p95_s": stage_results[1]["p95_s"],
        "requests": total,
        "failed": failed,
    }
    serve = {
        "metric": f"diurnal ramp through the gateway, {n} replicas, "
                  f"tiny [{backend}]",
        "backend": backend, "model": "tiny", "model_id": "tiny",
        "n_chips": len(jax.devices()), "replicas": n,
        "sessions": 16, "max_sessions": 16,
        "tok_per_s": round(tokens[0] / dt, 2),
        "trials": [round(tokens[0] / dt, 1)],
    }
    result = {**serve, "diurnal": diurnal}
    gw_srv.shutdown()
    gw.stop()
    for srv in servers:
        srv.shutdown()
    for cell in cells:
        cell.engine.stop()
    if args.out:
        write_artifact(args.out, serve, result)
    print(json.dumps(result), flush=True)


def phase_embed(args) -> None:
    """Embedding-cell throughput (BASELINE config 5: bge-base embedding
    serving): sequences/s for batched ~128-token inputs."""
    import numpy as np

    _own_the_chip()
    import jax

    from kukeon_tpu.models import bert
    from kukeon_tpu.parallel import auto_mesh_shape, make_mesh
    from kukeon_tpu.serving import EmbeddingEngine

    backend = _require_backend(jax.default_backend())
    n_chips = len(jax.devices())
    shape = auto_mesh_shape(n_chips)
    mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])

    if backend == "cpu":
        cfg, model_name, batch, seq_len, n_batches = (
            bert.bge_tiny(), "bge-tiny (cpu smoke)", 8, 32, 2)
    else:
        cfg, model_name, batch, seq_len, n_batches = (
            bert.bge_base(), "bge-base", 32, 128, 8)
    params = bert.init_params(jax.random.key(0), cfg)
    engine = EmbeddingEngine(cfg, params, mesh, batch_size=batch)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=seq_len).astype(np.int32)
               for _ in range(batch)]
    engine.warmup((seq_len,))
    t0 = time.monotonic()
    for _ in range(n_batches):
        vecs = engine.embed_batch(prompts)
    dt = time.monotonic() - t0
    print(json.dumps({
        "backend": backend, "model": model_name, "dim": int(vecs.shape[1]),
        "batch": batch, "seq_len": seq_len,
        "seq_per_s": round(batch * n_batches / dt, 1),
    }), flush=True)


def phase_ab(args) -> None:
    """Perf-lever A/B sweep (VERDICT r4 item 4): decode-chunk {4,16,64} and
    int8-KV on the flagship config, each arm in its own chip-owning
    subprocess. Prints one JSON line with every arm's tok/s. Run as
    `python bench.py --phase ab`."""
    backend, n_chips = detect_backend()
    _log(f"ab: backend={backend} n_chips={n_chips}")
    qdir = None
    if backend != "cpu":
        qdir = ensure_quantized_8b()
    arms = [
        ("chunk4", ["--decode-chunk", "4"]),
        ("chunk16", ["--decode-chunk", "16"]),
        ("chunk64", ["--decode-chunk", "64"]),
        ("chunk16+kvint8", ["--decode-chunk", "16", "--kv-int8"]),
    ]
    results: dict = {}
    for name, extra in arms:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", "serve"] + extra
        if qdir:
            cmd += ["--checkpoint", qdir]
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=2400, cwd=REPO, env=subprocess_env())
        except subprocess.TimeoutExpired:
            _log(f"ab arm {name}: timed out")
            results[name] = None
            continue
        if out.returncode != 0:
            _log(f"ab arm {name}: rc={out.returncode}\n{out.stderr[-1200:]}")
            results[name] = None
            continue
        serve = json.loads(out.stdout.strip().splitlines()[-1])
        results[name] = {"tok_per_s": round(serve["tok_per_s"], 2),
                         "trials": serve["trials"],
                         "latency_s": serve.get("latency_s")}
        _log(f"ab arm {name}: {results[name]}")
    line = {
        "metric": f"decode-chunk/kv-int8 A/B, 8B int8, {n_chips} chip(s) [{backend}]",
        "arms": results,
        "backend": backend,
    }
    print(json.dumps(line))
    if any(r is None for r in results.values()):
        sys.exit(1)


def phase_autotune(args) -> None:
    """Autotune sweep (the tentpole of the decode roofline campaign):
    decode-chunk × int8-KV × prefill-bucket arms, each measured by the
    serve phase in its own chip-owning subprocess, winner persisted to the
    serving tune profile (~/.kuke/serving_tune.json, KUKEON_TUNE_PATH to
    override) keyed by model+backend+chip-count. ServingEngine/ServingCell
    consult that profile at boot, so one sweep permanently configures
    production serving. Run as `python bench.py --autotune`; under
    JAX_PLATFORMS=cpu it sweeps the tiny model (the profile then keys as
    cpu and never leaks into TPU serving)."""
    backend, n_chips = detect_backend()
    _log(f"autotune: backend={backend} n_chips={n_chips}")
    qdir = None
    model_id = "tiny"
    if backend != "cpu":
        qdir = ensure_quantized_8b()
        model_id = "llama3-8b"

    # Arm grid. CPU smoke keeps it small (each arm boots a fresh engine);
    # TPU sweeps the full chunk ladder. The coarse-bucket arm measures
    # whether fewer/larger prefill buckets (fewer compiles, more padded
    # prefill compute) beat the default ladder for this workload.
    chunks = (4, 16, 64) if backend == "tpu" else (4, 16)
    coarse = "256,1024,4096" if backend == "tpu" else "64,256"
    arms: list[tuple[str, dict]] = []
    for c in chunks:
        for kv in (False, True):
            arms.append((f"chunk{c}" + ("+kvint8" if kv else ""),
                         {"decode_chunk": c, "kv_int8": kv,
                          "prefill_buckets": None}))
    arms.append((f"chunk{chunks[-1]}+coarse-buckets",
                 {"decode_chunk": chunks[-1], "kv_int8": False,
                  "prefill_buckets": coarse}))
    # Paged-KV arms: page size is an autotune lever like the others. The
    # serve phase sizes the pool to its slot count, so these arms measure
    # the gather/scatter overhead of the paged programs at steady state;
    # the concurrency upside at equal HBM is phase_mixed's measurement.
    for pt in ((64, 128) if backend == "tpu" else (16,)):
        arms.append((f"chunk{chunks[-1]}+paged{pt}",
                     {"decode_chunk": chunks[-1], "kv_int8": False,
                      "prefill_buckets": None, "kv_page_tokens": pt}))
    # Sharding-layout arms (the multi-chip sweep): every tensor-axis size
    # this host can factor (divisors of the chip count, capped at one ICI
    # ring) × KV pool sharded vs replicated. Size 1 is the baseline the
    # arms above already measure; a single-chip host grows no arms.
    for ms in (d for d in (2, 4, 8) if d <= n_chips and n_chips % d == 0):
        for kv in ("on", "off"):
            arms.append(
                (f"chunk{chunks[-1]}+mesh{ms}"
                 + ("+kvshard" if kv == "on" else "+kvrepl"),
                 {"decode_chunk": chunks[-1], "kv_int8": False,
                  "prefill_buckets": None, "chips": ms, "kv_shard": kv}))

    results: dict = {}
    best_name, best_cfg, best_rate = None, None, -1.0
    for name, cfg in arms:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", "serve",
               "--decode-chunk", str(cfg["decode_chunk"])]
        if cfg["kv_int8"]:
            cmd += ["--kv-int8"]
        if cfg["prefill_buckets"]:
            cmd += ["--prefill-buckets", cfg["prefill_buckets"]]
        if cfg.get("kv_page_tokens"):
            cmd += ["--kv-page-tokens", str(cfg["kv_page_tokens"])]
        if cfg.get("chips"):
            cmd += ["--chips", str(cfg["chips"]),
                    "--kv-shard", cfg.get("kv_shard", "auto")]
        if qdir:
            cmd += ["--checkpoint", qdir]
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=2400, cwd=REPO, env=subprocess_env())
        except subprocess.TimeoutExpired:
            _log(f"autotune arm {name}: timed out")
            results[name] = None
            continue
        if out.returncode != 0:
            _log(f"autotune arm {name}: rc={out.returncode}\n{out.stderr[-1200:]}")
            results[name] = None
            continue
        serve = json.loads(out.stdout.strip().splitlines()[-1])
        rate = float(serve["tok_per_s"])
        # Every arm is scored with the same product-instrument percentiles
        # the serve phase reports (p50/p95/p99 TTFT / inter-token / e2e):
        # the sweep record shows what each lever costs in tail latency,
        # not just what it buys in throughput.
        results[name] = {"tok_per_s": round(rate, 2),
                         "trials": serve["trials"],
                         "latency_s": serve.get("latency_s"),
                         "mesh": serve.get("mesh")}
        _log(f"autotune arm {name}: {results[name]}")
        if rate > best_rate:
            best_name, best_cfg, best_rate = name, cfg, rate

    line: dict = {
        "metric": f"autotune sweep, {model_id}, {n_chips} chip(s) [{backend}]",
        "arms": results,
        "backend": backend,
        "model": model_id,
    }
    if best_cfg is not None:
        sys.path.insert(0, REPO)
        from kukeon_tpu.serving import tuning

        buckets = (tuple(int(b) for b in best_cfg["prefill_buckets"].split(","))
                   if best_cfg["prefill_buckets"] else None)
        path = tuning.save(model_id, backend, n_chips, tuning.ServingTune(
            decode_chunk=best_cfg["decode_chunk"],
            kv_cache_int8=best_cfg["kv_int8"],
            prefill_buckets=buckets,
            kv_page_tokens=best_cfg.get("kv_page_tokens"),
            # Sharding layout of the winner: absent fields keep whatever
            # the cell's chip grant / divisibility default dictates.
            mesh_tensor=best_cfg.get("chips"),
            kv_shard={"on": True, "off": False}.get(
                best_cfg.get("kv_shard")),
            tok_per_s=best_rate,
        ))
        line["best"] = {"arm": best_name, "tok_per_s": round(best_rate, 2)}
        line["profile"] = {"path": path,
                           "key": tuning.profile_key(model_id, backend, n_chips)}
        _log(f"autotune: winner {best_name} ({best_rate:.1f} tok/s) -> {path}")
    else:
        line["error"] = "every arm failed; profile not written"
    print(json.dumps(line))
    if any(r is None for r in results.values()):
        sys.exit(1)


def phase_profile_layers(args) -> None:
    """Per-layer cost profiling (obs/profile.profile_layers): lower every
    transformer component (embed, each layer, head) individually at the
    prefill and decode shapes, record XLA cost-analysis FLOPs/bytes plus
    measured wall time, and persist the profile next to the serving tune
    keyed ``model|backend|n_chips`` — `kuke profile layers` renders it;
    the pipeline-split planner (ROADMAP item 2) consumes it. An armed
    ``profile.layers`` fault degrades to recorded per-component error
    entries and skips persistence — a clean reported failure, never a
    crashed bench."""
    _own_the_chip()
    import jax

    from kukeon_tpu.models import checkpoints, llama
    from kukeon_tpu.obs import profile as obs_profile
    from kukeon_tpu.parallel import auto_mesh_shape, make_mesh
    from kukeon_tpu.serving import tuning

    backend = _require_backend(jax.default_backend())
    n_chips = len(jax.devices())
    if args.checkpoint:
        params, cfg = checkpoints.load_quantized(args.checkpoint)
        model_id = "llama3-8b"
        prefill_len, decode_batch = 128, 4
    else:
        cfg = llama.llama_tiny()
        params = llama.init_params(jax.random.key(0), cfg)
        model_id = "tiny"
        prefill_len, decode_batch = 32, 2
    shape = auto_mesh_shape(n_chips)
    mesh = make_mesh(data=shape["data"], tensor=shape["tensor"])
    _log(f"profile-layers: {model_id} [{backend}] "
         f"prefill_len={prefill_len} decode_batch={decode_batch}")
    prof = obs_profile.profile_layers(
        params, cfg, mesh, prefill_len=prefill_len,
        decode_batch=decode_batch)
    key = tuning.profile_key(model_id, backend, n_chips)
    prof["key"] = key
    line = {"metric": f"per-layer cost profile, {model_id},"
                      f" {n_chips} chip(s) [{backend}]",
            "key": key,
            "num_layers": prof.get("num_layers"),
            "model_flops": prof.get("model_flops"),
            "model_bytes": prof.get("model_bytes"),
            "errors": prof.get("errors", 0)}
    if prof.get("errors"):
        line["failed"] = [c.get("name") for c in prof.get("components", ())
                          if c.get("error")]
        _log(f"profile-layers: {prof['errors']} component(s) failed; "
             "profile not persisted")
    else:
        line["path"] = tuning.save_layer_profile(
            model_id, backend, n_chips, prof)
        _log(f"profile-layers: persisted -> {line['path']}")
    print(json.dumps(line), flush=True)


# --- cold-start phase ---------------------------------------------------------

def _tail_file(path: str, limit: int = 2500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - limit))
            return f.read().decode(errors="replace")
    except OSError:
        return f"<unreadable: {path}>"


def _dump_evidence(run_path: str, daemon_log: str, cli: list[str],
                   socket_path: str, env: dict, run: int) -> None:
    """Preserve the crime scene on stderr BEFORE cleanup destroys it
    (VERDICT r4 weak 2: r4's cold-start failure was undiagnosable because
    rmtree ran before anything read the model-server log; the reference's
    e2e harness preserves daemon evidence — harness_daemon_test.go:26-60)."""
    import glob

    _log(f"=== cold-start run {run} evidence ===")
    try:
        got = subprocess.run(
            cli + ["--socket", socket_path, "--run-path", run_path,
                   "get", "cell", "llm", "--json"],
            env=env, capture_output=True, text=True, timeout=30,
        )
        _log("kuke get cell llm --json:\n" + (got.stdout or got.stderr)[-3000:])
    except Exception as e:  # noqa: BLE001 — evidence is best-effort
        _log(f"kuke get failed: {e}")
    for pattern, label in (
        (os.path.join(run_path, "**", "model-server", "container.log"),
         "model-server container.log"),
        (daemon_log, "daemon log"),
    ):
        paths = glob.glob(pattern, recursive=True) if "*" in pattern else [pattern]
        for p in paths:
            _log(f"--- {label} tail ({p}) ---\n{_tail_file(p)}")
    _log(f"=== end evidence (run {run}) ===")


def _cold_start_phases(port: int) -> dict:
    """Phase breakdown from the freshly-booted cell's own cold-start
    gauges (kukeon_cold_start_phase_seconds{phase=} + the total): the
    artifact records WHERE the boot time went (imports, init, compile,
    warmup, serve), not just the total — the ROADMAP item 4 attack
    surface. Best-effort: an older cell without the gauges yields {}."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            text = r.read().decode()
        from kukeon_tpu.obs import federate as fed

        fams = fed.parse(text)
        out: dict = {}
        fam = fams.get("kukeon_cold_start_phase_seconds")
        if fam is not None:
            for _n, labels, value in fam.samples:
                if labels.get("phase"):
                    # 3 decimals: the disk/cast/upload load sub-phases are
                    # millisecond-scale on the CPU tier and must survive.
                    out[labels["phase"]] = round(float(value), 3)
        total = fams.get("kukeon_cold_start_seconds")
        if total is not None and total.samples:
            out["total"] = round(float(total.samples[0][2]), 3)
        return out
    except Exception:  # noqa: BLE001 — phases are evidence, never a failure
        return {}


def measure_cold_starts(model: str, checkpoint: str | None, runs: int,
                        chips: str
                        ) -> tuple[list[float], list[str], list[dict]]:
    """N x [fresh daemon -> kuke apply model-cell manifest -> first
    /v1/health 200]. The daemon and model server are real subprocesses on
    the real CLI path (VERDICT item 2: 'time kuke apply of a model-cell
    manifest -> first /v1/health 200').

    Returns (times, errors, per-run phase breakdowns read off each booted
    cell's kukeon_cold_start_* gauges). A failed run dumps the model-server
    + daemon logs to stderr before its run path is removed, and the caller
    exits non-zero on any error after printing what was measured."""
    cli = [sys.executable, "-m", "kukeon_tpu.runtime.cli"]
    times: list[float] = []
    errors: list[str] = []
    phases: list[dict] = []
    for run in range(runs):
        run_path = tempfile.mkdtemp(prefix="kuke-bench-")
        socket_path = f"/tmp/kuked-bench-{uuid.uuid4().hex[:8]}.sock"
        daemon_log = os.path.join(run_path, "kukeond.log")
        port = 9600 + run
        env = subprocess_env()
        env.update({
            "KUKEON_TPU_CHIPS": chips,
            "KUKEOND_RECONCILE_INTERVAL": "1.0",
        })
        # hostNetwork: the timer polls 127.0.0.1; the in-policy model-cell
        # path is e2e-covered in tests/test_netpolicy_e2e.py.
        manifest = (
            "apiVersion: kukeon.io/v1beta1\n"
            "kind: Cell\n"
            "metadata: {name: llm}\n"
            "spec:\n"
            f"  model: {{model: {model}, chips: 1, port: {port}, numSlots: 4"
            + (f", checkpoint: {checkpoint}" if checkpoint else "")
            + ", maxSeqLen: 1024, hostNetwork: true}\n"
        )
        with open(daemon_log, "wb") as dlog:
            daemon = subprocess.Popen(
                cli + ["daemon", "serve", "--run-path", run_path,
                       "--socket", socket_path],
                env=env, stdout=dlog, stderr=subprocess.STDOUT,
            )
        try:
            deadline = time.monotonic() + 15
            while not os.path.exists(socket_path):
                if time.monotonic() > deadline:
                    raise RuntimeError("daemon socket did not appear")
                time.sleep(0.05)
            t0 = time.monotonic()
            subprocess.run(
                cli + ["--socket", socket_path, "--run-path", run_path,
                       "apply", "-f", "-"],
                input=manifest, text=True, env=env, check=True,
                capture_output=True, timeout=120,
            )
            health = f"http://127.0.0.1:{port}/v1/health"
            budget = float(os.environ.get("KUKEON_BENCH_HEALTH_TIMEOUT", "600"))
            deadline = time.monotonic() + budget
            while True:
                try:
                    with urllib.request.urlopen(health, timeout=2) as r:
                        if r.status == 200:
                            break
                except OSError:
                    pass
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"model cell not healthy in {budget:.0f}s (run {run})"
                    )
                time.sleep(0.25)
            dt = time.monotonic() - t0
            times.append(dt)
            ph = _cold_start_phases(port)
            if ph:
                phases.append(ph)
                _log(f"cold start run {run}: {dt:.1f}s "
                     + " ".join(f"{k}={v}s" for k, v in sorted(ph.items())))
            else:
                _log(f"cold start run {run}: {dt:.1f}s")
            subprocess.run(
                cli + ["--socket", socket_path, "--run-path", run_path,
                       "delete", "cell", "llm", "--force"],
                env=env, capture_output=True, timeout=120,
            )
        except Exception as e:  # noqa: BLE001 — keep the other runs' numbers; main() exits 1
            errors.append(f"run {run}: {e}")
            _log(f"cold start run {run} FAILED: {e}")
            _dump_evidence(run_path, daemon_log, cli, socket_path, env, run)
        finally:
            daemon.terminate()
            try:
                daemon.wait(timeout=5)
            except subprocess.TimeoutExpired:
                daemon.kill()
            import shutil

            shutil.rmtree(run_path, ignore_errors=True)
            if os.path.exists(socket_path):
                os.unlink(socket_path)
    return times, errors, phases


# --- orchestrator -------------------------------------------------------------

def _cold_summary(runs_s: list[float], errors: list[str],
                  phases: list[dict], model: str) -> dict:
    """The artifact's cold_start section from measure_cold_starts output."""
    cold: dict = {
        "target_s": COLD_START_TARGET_S,
        "runs_s": [round(t, 1) for t in sorted(runs_s)],
        "model": model,
    }
    if runs_s:
        s = sorted(runs_s)
        cold["p50_s"] = round(s[len(s) // 2], 1)
    if phases:
        # Per-run boot-phase breakdowns (kukeon_cold_start_phase_seconds
        # read off each booted cell): the artifact names where cold-start
        # time goes, not just how much of it there was.
        cold["phases_s"] = phases
        # v6: the streamed-load sub-phases (disk / cast / upload) are
        # WORK-TIME ledgers overlapped with each other and with compile,
        # summarized as medians — so sum(phases) > total is the overlap
        # evidence, not an accounting bug.
        load = {}
        for stage in ("disk", "cast", "upload"):
            vals = sorted(p[stage] for p in phases if stage in p)
            if vals:
                load[stage] = round(vals[len(vals) // 2], 3)
        if load:
            cold["load_s"] = load
    if errors:
        cold["error"] = "; ".join(errors)[-500:]
    return cold


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", default="all",
                    choices=["all", "serve", "embed", "ab", "autotune",
                             "gateway", "mixed", "disagg", "diurnal",
                             "profile-layers"])
    # Diurnal ramp through the gateway + spillover (phase_diurnal): the
    # night->peak->trough arrival schedule with a deliberately
    # under-provisioned fleet; the headline numbers are zero client-visible
    # 429s during the peak's shed storm and the per-stage client p95.
    ap.add_argument("--diurnal", action="store_true")
    # Mixed agent-session workload at fixed KV HBM (phase_mixed): legacy
    # vs paged engine, max concurrent sessions + aggregate tok/s per arm.
    ap.add_argument("--mixed", action="store_true")
    # Disaggregated prefill/decode acceptance bench (phase_disagg, run as
    # `--mixed --disagg`): the bimodal workload against a 1-prefill +
    # 1-decode split vs the same cells mixed, through the real gateway;
    # client-side TTFT p95 per arm + the handoff cost histogram.
    ap.add_argument("--disagg", action="store_true")
    # Scale-out routing benchmark: stand up a replica gateway + N in-process
    # replicas and measure aggregate tok/s + retry rate through the proxy.
    ap.add_argument("--replicas", type=int, default=1)
    # Sweep the serving perf levers and persist the winner to the tune
    # profile that ServingEngine/ServingCell read at boot (phase_autotune).
    ap.add_argument("--autotune", action="store_true")
    # Per-layer cost profiling (phase_profile_layers): lower each model
    # component individually, record cost-analysis FLOPs/bytes + wall
    # time, persist next to the serving tune for `kuke profile layers`.
    ap.add_argument("--profile-layers", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--decode-chunk", type=int,
                    default=int(os.environ.get("KUKEON_BENCH_CHUNK", "16")))
    # int8 KV cache (halves the per-step cache HBM stream; the win grows
    # with context length and slot count — at the default 4x1024 shapes the
    # cache is ~6% of step bytes next to 8 GB of int8 weights).
    ap.add_argument("--kv-int8", action="store_true",
                    default=os.environ.get("KUKEON_BENCH_KV_INT8", "") == "1")
    # Comma-separated prefill bucket ladder override (e.g. "256,1024,4096").
    ap.add_argument("--prefill-buckets", default=None)
    # Paged KV cache page size (serving/kv_pages.py): 0/absent = legacy
    # contiguous layout; > 0 = block-table page pool with this page size.
    ap.add_argument("--kv-page-tokens", type=int, default=None)
    # Sharding layout (serve phase): exact N-chip tensor-parallel mesh
    # (absent = every visible device, auto-factorized) and whether the KV
    # pool shards over the tensor axis (auto = the engine's divisibility
    # default). The autotune sweep drives both.
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--kv-shard", choices=("auto", "on", "off"),
                    default="auto")
    # Fast mode: measure the streamed-boot cold start ONLY (fresh daemon ->
    # apply -> first health, with the disk/cast/upload/compile breakdown
    # off the cell's own gauges) and skip the serve/flood phases entirely —
    # the boot-pipeline iteration loop in seconds, not minutes.
    ap.add_argument("--cold-start-only", action="store_true")
    ap.add_argument("--cold-runs", type=int, default=None,
                    help="override the number of cold-start runs")
    # Standardized trajectory artifact (e.g. --out BENCH_r06.json): one
    # schema-versioned JSON file per run (kukeon-bench/v8; read_artifact
    # upgrades v1-v7 points) with percentiles, throughput, compile counts,
    # peak HBM, replica count, and the disaggregation + diurnal sections,
    # so BENCH_*.json points stay comparable across rounds regardless of
    # how the console line evolves.
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.autotune or args.phase == "autotune":
        phase_autotune(args)
        return
    if args.profile_layers or args.phase == "profile-layers":
        phase_profile_layers(args)
        return
    if args.disagg or args.phase == "disagg":
        phase_disagg(args)
        return
    if args.diurnal or args.phase == "diurnal":
        phase_diurnal(args)
        return
    if args.mixed or args.phase == "mixed":
        phase_mixed(args)
        return
    if args.phase == "gateway" or args.replicas > 1:
        phase_gateway(args)
        return
    if args.phase == "serve":
        phase_serve(args)
        return
    if args.phase == "embed":
        phase_embed(args)
        return
    if args.phase == "ab":
        phase_ab(args)
        return

    backend, n_chips = detect_backend()
    _log(f"backend={backend} n_chips={n_chips}")

    # Host-only numpy work (no JAX backend is initialized in this
    # orchestrator, before or after it starts the chip-owning children).
    qdir = ensure_quantized_8b() if backend != "cpu" else None
    cold_model, cold_runs = ("llama3-8b", 3) if qdir else ("tiny", 1)
    if args.cold_runs is not None:
        cold_runs = args.cold_runs

    if args.cold_start_only:
        runs_s, errs, ph = measure_cold_starts(
            cold_model, qdir, cold_runs,
            chips=os.environ.get("KUKEON_TPU_CHIPS", "0"))
        result = {"cold_start": _cold_summary(runs_s, errs, ph, cold_model)}
        if args.out:
            # The serve phase never ran: the artifact records the boot
            # breakdown with the serve fields explicitly null, so trend
            # tooling sees "not measured", not "measured zero".
            write_artifact(args.out, {
                "backend": backend, "n_chips": n_chips, "model": cold_model,
                "sessions": None, "tok_per_s": 0.0, "trials": 0,
            }, result)
        print(json.dumps(result))
        if errs:
            sys.exit(1)
        return

    def run_phase(phase: str, extra: list[str], timeout: float) -> dict:
        """One measuring phase in its own process (it owns the chip and
        exits -> releases it for the next child); its last stdout line is
        the result, a non-zero exit ends the bench."""
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", phase]
            + extra, capture_output=True, text=True, timeout=timeout,
            cwd=REPO, env=subprocess_env())
        sys.stderr.write(out.stderr[-8000:])
        if out.returncode != 0:
            raise RuntimeError(f"{phase} phase rc={out.returncode}")
        result = json.loads(out.stdout.strip().splitlines()[-1])
        _log(f"{phase} phase result: {json.dumps(result)}")
        return result

    serve = run_phase(
        "serve",
        ["--decode-chunk", str(args.decode_chunk)]
        + (["--kv-int8"] if args.kv_int8 else [])
        + (["--checkpoint", qdir] if qdir else []),
        timeout=3600)
    # Embedding throughput (config 5).
    embedding = run_phase("embed", [], timeout=1200)

    baseline_share = 1500.0 * serve["n_chips"] / 8.0
    result = {
        "metric": "aggregate decode tok/s, %d concurrent sessions, %s, %d chip(s) [%s]"
                  % (serve["sessions"], serve["model"], serve["n_chips"],
                     serve["backend"]),
        "value": round(serve["tok_per_s"], 2),
        "unit": "tok/s",
        "backend": serve["backend"],
        "vs_baseline": round(serve["tok_per_s"] / baseline_share, 4),
        "trials": serve["trials"],
        # p50/p95/p99 TTFT / inter-token / e2e from the serving engine's
        # own obs histograms (the same ones /metrics exposes in prod).
        "latency_s": serve.get("latency_s"),
    }

    cold_runs_s, cold_errors, cold_phases = measure_cold_starts(
        cold_model, qdir, cold_runs,
        chips=os.environ.get("KUKEON_TPU_CHIPS", "0"),
    )
    result["cold_start"] = _cold_summary(
        cold_runs_s, cold_errors, cold_phases, cold_model)
    result["embedding"] = embedding
    if args.out:
        write_artifact(args.out, serve, result)
    print(json.dumps(result))
    if cold_errors:
        # The line above carries what was measured; a lost cold-start run
        # is still a failed phase.
        sys.exit(1)


def read_artifact(path: str) -> dict:
    """Read a BENCH_rNN.json trajectory artifact, upgrading older schemas
    in place so trajectory tooling compares one shape across rounds: a
    kukeon-bench/v1 point (pre-gateway) is a single-engine measurement and
    gains ``replicas: 1``; v1/v2 points (pre-paged-KV) gain
    ``kv_page_tokens: 0`` (the legacy contiguous layout) and
    ``max_sessions`` equal to their session count; v1–v3 points
    (pre-disaggregation) gain ``ttft_p95_s`` (lifted from their latency
    percentiles when present), ``handoff_ms_p50: None`` (no KV handoff
    existed), and ``disagg: None``; v1–v4 points (pre-autoscaling) gain
    ``diurnal: None`` (no diurnal-ramp phase existed); v1–v5 points
    (pre-streamed-boot) gain ``cold_start.load_s: None`` (no disk / cast /
    upload sub-phase ledger existed before the streamed checkpoint
    pipeline); v1–v6 points (pre-multi-chip) gain ``mesh: None`` (the
    measurement ran before the sharded serving mesh existed); v1–v7
    points (pre-roofline) gain ``program_costs: None`` and ``mfu: None``
    (no per-program timer/cost instrumentation existed — a v8 point
    always records both when the serve phase ran)."""
    with open(path) as f:
        artifact = json.load(f)
    schema = artifact.get("schema")
    if schema not in ("kukeon-bench/v1", "kukeon-bench/v2",
                      "kukeon-bench/v3", "kukeon-bench/v4",
                      "kukeon-bench/v5", "kukeon-bench/v6",
                      "kukeon-bench/v7", "kukeon-bench/v8"):
        raise ValueError(f"unknown bench artifact schema {schema!r} in {path}")
    if schema != "kukeon-bench/v8":
        artifact = dict(artifact)
        artifact.setdefault("replicas", 1)              # v1 -> v2
        artifact.setdefault("kv_page_tokens", 0)        # v2 -> v3
        artifact.setdefault("max_sessions", artifact.get("sessions"))
        lat = ((artifact.get("latency_s") or {}).get("ttft") or {})
        artifact.setdefault("ttft_p95_s", lat.get("p95"))   # v3 -> v4
        artifact.setdefault("handoff_ms_p50", None)
        artifact.setdefault("disagg", None)
        artifact.setdefault("diurnal", None)            # v4 -> v5
        if isinstance(artifact.get("cold_start"), dict):    # v5 -> v6
            artifact["cold_start"] = dict(artifact["cold_start"])
            artifact["cold_start"].setdefault("load_s", None)
        artifact.setdefault("mesh", None)               # v6 -> v7
        artifact.setdefault("program_costs", None)      # v7 -> v8
        artifact.setdefault("mfu", None)
        artifact["schema"] = "kukeon-bench/v8"
    return artifact


def write_artifact(path: str, serve: dict, result: dict) -> None:
    """The standardized BENCH_rNN.json trajectory point: fixed schema, one
    file per run, every field from the product's own instruments."""
    artifact = {
        "schema": "kukeon-bench/v8",
        "at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": serve["backend"],
        "n_chips": serve["n_chips"],
        "model": serve.get("model_id") or serve["model"],
        # v2: how many serving engines stood behind the measurement (the
        # gateway phase sets >1; the classic serve phase is one engine).
        "replicas": serve.get("replicas", 1),
        "sessions": serve["sessions"],
        "tok_per_s": round(serve["tok_per_s"], 2),
        "trials": serve["trials"],
        "vs_baseline": result.get("vs_baseline"),
        # p50/p95/p99 for ttft / inter_token / e2e (engine histograms).
        "latency_s": serve.get("latency_s"),
        "compiles": serve.get("compiles"),
        "peak_hbm_bytes": serve.get("peak_hbm_bytes"),
        # v3: KV page size the measured engine served from (0 = legacy
        # contiguous layout) and the peak number of concurrently resident
        # sessions — the paged cache's headline number (--mixed drives it
        # past the legacy slot count at equal HBM).
        "kv_page_tokens": serve.get(
            "kv_page_tokens", (serve.get("config") or {}).get(
                "kv_page_tokens", 0)),
        "max_sessions": serve.get("max_sessions", serve.get("sessions")),
        # v4: client-observable TTFT p95 (lifted from the engine latency
        # percentiles when the phase measured no client-side number), and
        # the disaggregated-serving section (KV handoff cost + per-arm
        # TTFT/throughput) when `--mixed --disagg` produced one.
        "ttft_p95_s": serve.get(
            "ttft_p95_s",
            ((serve.get("latency_s") or {}).get("ttft") or {}).get("p95")),
        "handoff_ms_p50": result.get("handoff_ms_p50"),
        "disagg": result.get("disagg"),
        # v5: the diurnal-ramp section (per-stage qps/p95/statuses plus
        # the gateway spillover outcome counters) when `--diurnal` ran.
        "diurnal": result.get("diurnal"),
        "cold_start": result.get("cold_start"),
        "embedding": result.get("embedding"),
        "mixed": result.get("mixed"),
        # v7: the serving-mesh layout the measurement ran on (chips,
        # tensor-axis size, whether the KV pool sharded); None only for
        # phases that never built an engine (e.g. --cold-start-only).
        "mesh": serve.get("mesh"),
        # v8: the roofline section — per-program dispatch/wall/token
        # counters with their static cost-analysis FLOPs/bytes (the
        # ProgramTimers snapshot) and the headline MFU; None for phases
        # that never ran the serve loop.
        "program_costs": serve.get("program_costs"),
        "mfu": serve.get("mfu"),
    }
    # v6: cold_start carries the streamed-load sub-phase ledger (disk /
    # cast / upload medians); explicit None when the boot exported none.
    if isinstance(artifact["cold_start"], dict):
        artifact["cold_start"].setdefault("load_s", None)
    try:
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
        _log(f"wrote trajectory artifact {path}")
    except OSError as e:
        _log(f"could not write {path}: {e}")


if __name__ == "__main__":
    main()
