"""The engine loop's spans and the counters at the same boundaries
(obs/spans.py, serving/engine.py): prefill tokens by kind against sums worked
out from the prompts and buckets, the loop's wall time partitioned by phase,
the step record's host_s, and the capture's Python-tracer option."""

import glob
import time

import jax
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.obs import ProfileSpool, Registry
from kukeon_tpu.obs.spans import LOOP_PHASES, STEP_PHASES, LoopSpans
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine
from kukeon_tpu.serving.engine import bucket_length


def _engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 2)
    return ServingEngine(cfg, params, mesh, max_seq_len=256, decode_chunk=4,
                         **kw)


def _run(eng, prompt, prefix_id=None, new=3):
    req = eng.submit(np.asarray(prompt, np.int32),
                     SamplingParams(max_new_tokens=new), prefix_id=prefix_id)
    while not req.done.is_set():
        eng.step()
    return req


def _tokens(eng):
    return {k: eng._m_prefill_tokens.value(kind=k)
            for k in ("real", "padded", "cached")}


def _phases(eng):
    return {p: eng.spans._seconds.value(phase=p) for p in LOOP_PHASES}


@pytest.mark.parametrize("paged", [False, True])
def test_prefill_tokens_by_kind_equal_the_sums_from_prompts_and_buckets(paged):
    eng = _engine(prefix_cache_size=4, **(
        dict(kv_page_tokens=16, kv_pool_pages=64) if paged else {}))
    head = list(range(1, 81))                     # 80 tokens
    plain = [list(range(5, 5 + n)) for n in (8, 70, 130)]
    want = {"real": 0, "padded": 0, "cached": 0}
    for p in plain:                               # no prefixId: all run
        _run(eng, p)
        want["real"] += len(p)
        want["padded"] += bucket_length(len(p), eng.prefill_buckets)
    # a session: the first turn misses and is stored, the later turns run
    # only what the store does not hold. The dense store keeps each turn's
    # whole prompt; the paged one shares the first turn's FULL pages (80 rows
    # are five pages of 16) and is not re-pointed on a hit.
    stored = 0
    for grown in (head, head + [7] * 20, head + [7] * 20 + [9] * 50):
        before = _tokens(eng)
        _run(eng, grown, prefix_id="s")
        got = {k: _tokens(eng)[k] - before[k] for k in before}
        tail = len(grown) - stored
        assert got == {"real": tail, "cached": stored,
                       "padded": bucket_length(tail, eng.prefill_buckets)}
        for k in want:
            want[k] += got[k]
        stored = (stored or len(grown) // 16 * 16) if paged else len(grown)
    assert _tokens(eng) == want
    assert eng.prefix_hits == 2 and want["cached"] == (160 if paged else 180)
    # the program counter the benchmark already reads counts the same padding
    assert eng.timers._m_tokens.value(program="prefill") == want["padded"]


def test_loop_seconds_partition_the_loop_threads_wall_time():
    eng = _engine()
    _run(eng, list(range(1, 20)))                 # compile outside the clock
    base = _phases(eng)
    steps0 = eng._m_steps.value()
    t0 = time.monotonic()
    eng.start()
    try:
        reqs = [eng.submit(np.arange(1, 30 + i, dtype=np.int32),
                           SamplingParams(max_new_tokens=40))
                for i in range(4)]
        for r in reqs:
            assert r.done.wait(timeout=120)
        time.sleep(1.0)                           # some idle_wait too
    finally:
        eng.stop()
    wall = time.monotonic() - t0
    assert eng._thread is None
    got = {p: _phases(eng)[p] - base[p] for p in LOOP_PHASES}
    assert all(v >= 0 for v in got.values())
    assert sum(got.values()) == pytest.approx(wall, rel=0.01)
    assert got["idle_wait"] >= 0.9
    for p in ("admit", "decode_dispatch", "fetch_chunk", "emit", "other"):
        assert got[p] > 0, p
    assert eng._m_steps.value() - steps0 >= 10


def test_the_step_record_carries_the_steps_seconds_by_phase():
    eng = _engine()
    _run(eng, list(range(1, 20)), new=9)
    recs = eng.recorder.snapshot()
    assert recs and all("host_s" in r for r in recs)
    for r in recs:
        assert set(r["host_s"]) <= set(STEP_PHASES) | {"other"}
        assert sum(r["host_s"].values()) == pytest.approx(r["wall_s"],
                                                          abs=2e-5)
    assert "fetch_first" in recs[0]["host_s"] and recs[0]["prefills"] == 1
    assert any("fetch_chunk" in r["host_s"] for r in recs)


def test_nested_phases_are_charged_their_self_time():
    spans = LoopSpans(Registry())
    t0 = time.monotonic()
    with spans.span("engine.step"):
        with spans.span("engine.decode_dispatch"):
            time.sleep(0.01)
            t1 = time.monotonic()
            with spans.span("engine.fetch_chunk", k=4):
                time.sleep(0.03)
            t2 = time.monotonic()
            with spans.span("engine.prefill_dispatch", slot=0) as sp:
                sp.set(real=3)              # no phase: inside its parent
                time.sleep(0.01)
        host_s = spans.host_s(time.monotonic() - t0)
    t3 = time.monotonic()
    val = {p: spans._seconds.value(phase=p) for p in LOOP_PHASES}
    assert val["fetch_chunk"] == pytest.approx(t2 - t1, abs=2e-3)
    assert val["decode_dispatch"] == pytest.approx(t3 - t0 - (t2 - t1),
                                                   abs=2e-3)
    assert 0 <= val["other"] < 2e-3
    assert sum(val.values()) == pytest.approx(t3 - t0, abs=2e-3)
    assert val["idle_wait"] == val["admit"] == 0.0
    assert set(host_s) == {"decode_dispatch", "fetch_chunk", "other"}


@pytest.mark.parametrize("python_tracer", [False, True])
def test_a_capture_traces_python_only_when_asked(tmp_path, python_tracer):
    from jax.profiler import ProfileData

    spool = ProfileSpool(base_dir=str(tmp_path / "spool"))
    rec = spool.start(300, python_tracer=python_tracer)
    assert rec["pythonTracer"] is python_tracer

    def busy():                         # a Python frame the tracer would see
        return sum(range(1000))

    deadline = time.monotonic() + 30
    while spool.list()[0]["state"] == "running":
        busy()
        assert time.monotonic() < deadline
        time.sleep(0.005)
    (path,) = glob.glob(str(tmp_path / "spool" / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for ev in ln.events}
    assert any("busy" in n for n in names) is python_tracer


def test_the_programs_carry_the_named_scopes_and_no_other_change():
    """jax.named_scope is metadata: the scopes are in the lowering's debug
    locations, and the text without them is what it was (the compile-cache key
    leaves locations out, so scoped and unscoped programs share one entry)."""
    import re

    eng = _engine()
    eng._ensure_loaded()
    key = jax.random.key(1)
    f32, i32 = np.float32, np.int32
    with jax.set_mesh(eng.mesh):
        decode = eng._decode_chunk.lower(
            eng.params, eng.state, key, np.zeros(2, f32), np.zeros(2, i32),
            np.ones(2, f32), 4)
        prefill = eng._prefill.lower(
            eng.params, np.zeros((1, 64), i32), 5, key, f32(0), i32(0), f32(1))

    def scopes(lowered):
        names = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
        return {part for n in names for part in n.split("/")}

    model = {"embed", "attn_norm", "qkv", "rope", "attention", "wo",
             "mlp_norm", "mlp", "lm_head", "sample"}
    assert model | {"kv_insert"} <= scopes(decode)
    assert model | {"kv_insert"} <= scopes(prefill)
    assert "attention" not in decode.as_text()
