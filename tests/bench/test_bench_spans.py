"""The readers of the program's own spans and counters (benchmark/span_reduce.py,
layer_metrics/_spans.py and the metrics on top of them): over a parent-shaped
run they return None and never raise; over hand-made and test-made captures
they return the hand-computed values; and the in-process harness reports them
in a traced run."""

import glob
import gzip
import json
import os
import shutil
import threading
import time

import pytest

import inproc
from benchmark import plugins, run, span_reduce as sr
from benchmark.layer_metrics import _spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["first_token_after_prefill_ms", "prefill_pad_share",
       "prefix_token_hit_share", "host_ms_per_step", "idle_in_host_work_share"]
SPAN_READ = {"first_token_after_prefill_ms", "idle_in_host_work_share"}


def _reader(name):
    return plugins.load("layer_metrics", name)


def _entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m for m in json.load(f)["per_layer"] if m["name"] in NEW]


def test_benchmark_json_lists_every_new_reader_with_its_cells():
    got = {m["name"]: m for m in _entries()}
    assert sorted(got) == sorted(NEW)
    for m in got.values():
        assert m["workloads"] and m["source"] in ("program_span",
                                                  "program_counter")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert got["prefix_token_hit_share"]["workloads"] == [
        "mistral7b.agent-sessions"]


# --- a parent-shaped run: no span, no new counter family ---------------------

def _recorded_capture(path):
    """read_capture() of the planes recorded on the chip before the spans
    existed (tests/bench/fixtures/trace-small.json.gz), with the Python
    tracer's frames such a capture holds on /host:CPU."""
    with gzip.open(os.path.join(inproc.FIXTURES, "trace-small.json.gz"),
                   "rt") as f:
        planes = json.load(f)["planes"]
    lines = planes[0]["lines"]
    frames = [("$engine.py:1756 step", 0.01 * i, 0.005, {}) for i in range(5)]
    host = [e for e in frames if e[0].startswith(("engine.", "cell."))]
    ops = [(s, d) for _n, s, d in lines["XLA Ops"]]
    mods = [tuple(e) for e in lines["XLA Modules"]]
    every = [(s, s + d) for _n, s, d in lines["XLA Ops"] + lines["XLA Modules"]]
    return {"host": host, "ops": ops, "modules": mods,
            "window": (min(a for a, _b in every), max(b for _a, b in every))}


PARENT_SCRAPE_OPEN = {
    "kukeon_engine_tokens_total": [({}, 100.0)],
    "kukeon_engine_prefix_cache_total": [({"result": "hit"}, 3.0),
                                         ({"result": "miss"}, 1.0)],
    "kukeon_program_tokens_total": [({"program": "prefill"}, 2048.0)],
    "kukeon_engine_prefill_seconds_count": [({"bucket": "64"}, 4.0)],
}
PARENT_SCRAPE_CLOSE = {k: [(lb, v * 3) for lb, v in rows]
                       for k, rows in PARENT_SCRAPE_OPEN.items()}


@pytest.fixture
def parent_ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(sr, "read_capture", _recorded_capture)
    cap = tmp_path / "capture-parent"
    cap.mkdir()
    red = sr.reduce(str(cap))
    assert red == {"spans": {}}
    (cap / "span_reduction.json").write_text(json.dumps(red))
    return {"capture": {"rec": {"path": str(cap)}, "requested": 0.0,
                        "duration_s": 3.0,
                        "metrics_before": PARENT_SCRAPE_OPEN,
                        "metrics_after": PARENT_SCRAPE_CLOSE},
            "metrics_open": PARENT_SCRAPE_OPEN,
            "metrics_close": PARENT_SCRAPE_CLOSE, "records": []}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_over_a_parent_shaped_run(name, parent_ctx,
                                                        capsys):
    assert _reader(name).read(parent_ctx) is None
    sr.show(json.loads(open(os.path.join(
        parent_ctx["capture"]["rec"]["path"], "span_reduction.json")).read()))
    assert "no engine.* or cell.* event" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_where_the_reduction_fails(name, tmp_path,
                                                         parent_ctx):
    """span_reduce.py exits non-zero over a directory with no capture: the
    span readers answer None once, and the reduction is not tried again."""
    ctx = {**parent_ctx, "capture": {**parent_ctx["capture"],
                                     "rec": {"path": str(tmp_path / "none")}}}
    assert _reader(name).read(ctx) is None
    if name in SPAN_READ:
        assert ctx["_spans"] is None
    assert _reader(name).read({**parent_ctx, "capture": {}}) is None


@pytest.mark.parametrize("scrapes", [
    ({}, {}),
    ({_spans.PREFILL_TOKENS: [({"kind": "real"}, 5.0)]},
     {_spans.PREFILL_TOKENS: [({"kind": "real"}, 5.0)]}),          # no growth
    ({}, {_spans.PREFILL_TOKENS: [({"kind": "cached"}, 9.0)],      # no real
          _spans.LOOP: [({"phase": "admit"}, 1.0)]}),              # no steps
    ({}, {_spans.STEPS: [({}, 4.0)]}),                             # no seconds
])
def test_counter_readers_return_none_on_an_absent_or_flat_family(scrapes):
    ctx = {"metrics_open": scrapes[0], "metrics_close": scrapes[1]}
    for name in ("prefill_pad_share", "prefix_token_hit_share",
                 "host_ms_per_step"):
        assert _reader(name).read(ctx) is None


def test_counter_readers_on_hand_made_scrapes():
    def scrape(real, padded, cached, steps, phases):
        return {
            _spans.PREFILL_TOKENS: [({"kind": "real"}, real),
                                    ({"kind": "padded"}, padded),
                                    ({"kind": "cached"}, cached)],
            _spans.STEPS: [({}, steps)],
            _spans.LOOP: [({"phase": p}, s) for p, s in phases.items()]}

    phases0 = dict(admit=1.0, decode_dispatch=1.0, fetch_first=5.0,
                   fetch_chunk=50.0, emit=1.0, idle_wait=9.0, other=1.0)
    phases1 = dict(admit=1.3, decode_dispatch=1.2, fetch_first=6.0,
                   fetch_chunk=90.0, emit=1.4, idle_wait=10.0, other=1.1)
    ctx = {"metrics_open": scrape(100, 200, 50, 10, phases0),
           "metrics_close": scrape(400, 600, 950, 210, phases1)}
    assert _reader("prefill_pad_share").read(ctx) == pytest.approx(25.0)
    assert _reader("prefix_token_hit_share").read(ctx) == pytest.approx(75.0)
    # (0.3 + 0.2 + 0.4 + 0.1) s of host work over 200 steps
    assert _reader("host_ms_per_step").read(ctx) == pytest.approx(5.0)


# --- a hand-made capture: the arithmetic against the device ------------------

def _hand_capture(_path):
    """Two steps and an idle wait. Device 0 runs a decode chunk (0.00-0.10),
    then nothing until the prefill the second step dispatches (0.13-0.16),
    then the next chunk (0.17-0.27); the window ends at 0.30.

    host: step A 0.000-0.105 = admit 0.000-0.001, decode_dispatch
    0.001-0.003, fetch_chunk 0.003-0.101, emit 0.101-0.104;
    idle_wait 0.105-0.118; step B 0.120-0.280 = admit 0.121-0.131 (one
    prefill_dispatch 0.122-0.130), decode_dispatch 0.131-0.133, fetch_first
    0.133-0.165, emit 0.165-0.167 (first_token at 0.166), fetch_chunk
    0.167-0.271, emit 0.271-0.279.
    idle gaps: 0.10-0.13 (fetch_chunk 1, emit 3, other 1+1, idle_wait 13,
    between the spans 2, admit 9 ms), 0.16-0.17 (fetch_first 5, emit 2,
    fetch_chunk 3), 0.27-0.30 (fetch_chunk 1, emit 8, other 1; its last 20 ms
    lie after the last recorded span and are left out)."""
    def sp(name, a, b, **st):
        return (name, a, b - a, st)

    host = [
        ("$engine.py:1 _loop", 0.0, 0.3, {}),
        sp("engine.step", 0.000, 0.105),
        sp("engine.admit", 0.000, 0.001, free=0, queued=0),
        sp("engine.decode_dispatch", 0.001, 0.003, k=16, active=2,
           live_rows=900),
        sp("engine.fetch_chunk", 0.003, 0.101, k=16),
        sp("engine.emit", 0.101, 0.104, tokens=32),
        sp("engine.idle_wait", 0.105, 0.118),
        sp("engine.step", 0.120, 0.280),
        sp("engine.admit", 0.121, 0.131, free=1, queued=1),
        sp("engine.prefill_dispatch", 0.122, 0.130, request="r1", slot=2,
           program="prefill_ext", hit=1, cached=1000, real=100, padded=128),
        sp("engine.decode_dispatch", 0.131, 0.133, k=16, active=3,
           live_rows=2000),
        sp("engine.fetch_first", 0.133, 0.165, n=1),
        sp("engine.emit", 0.165, 0.167, tokens=1),
        sp("engine.first_token", 0.166, 0.1661, request="r1"),
        sp("engine.fetch_chunk", 0.167, 0.271, k=16),
        sp("engine.emit", 0.271, 0.279, tokens=48),
        sp("cell.generate", 0.110, 0.112, request="r1"),
    ]
    host = [e for e in host if e[0].startswith(("engine.", "cell."))]
    ops = [(0.00, 0.10), (0.00, 0.06), (0.06, 0.04), (0.13, 0.03),
           (0.17, 0.10)]
    mods = [("jit_decode_chunk_fn(1)", 0.00, 0.10),
            ("jit_prefill_ext(2)", 0.13, 0.03),
            ("jit_decode_chunk_fn(1)", 0.17, 0.10),
            ("jit_insert(3)", 0.2999, 0.0001)]
    return {"host": host, "ops": ops, "modules": mods, "window": (0.0, 0.30)}


@pytest.fixture
def hand_ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(sr, "read_capture", _hand_capture)
    cap = tmp_path / "capture-hand"
    cap.mkdir()
    red = json.loads(json.dumps(sr.reduce(str(cap))))
    (cap / "span_reduction.json").write_text(json.dumps(red))
    tokens = {_spans.PREFILL_TOKENS: [({"kind": "real"}, 100.0),
                                      ({"kind": "padded"}, 128.0),
                                      ({"kind": "cached"}, 1000.0)]}
    return {"capture": {"rec": {"path": str(cap)}, "metrics_before": {},
                        "metrics_after": tokens}}, red


def test_idle_time_goes_to_the_phase_the_loop_was_in(hand_ctx, capsys):
    ctx, red = hand_ctx
    assert red["spans"]["engine.step"] == {"count": 2,
                                           "seconds": pytest.approx(0.265)}
    assert red["spans"]["cell.generate"]["count"] == 1
    assert red["covered"] == [0.0, pytest.approx(0.28)]
    assert red["idle_s"] == pytest.approx(0.05)
    assert red["idle_outside_s"] == pytest.approx(0.02)
    by = red["idle_by_phase"]
    want = {"fetch_chunk": 0.005, "emit": 0.013, "other": 0.003,
            "idle_wait": 0.013, "admit": 0.009, "fetch_first": 0.005,
            "unattributed": 0.002}
    assert {k: round(v, 6) for k, v in by.items()} == want
    assert sum(by.values()) == pytest.approx(red["idle_s"])
    # the host made the device wait in admit + emit + other: 25 of 50 ms
    got = _reader("idle_in_host_work_share").read(ctx)
    assert got == pytest.approx(50.0)
    out = capsys.readouterr().out
    assert "counters {\"real\": 100.0, \"padded\": 128.0, \"cached\": 1000.0}" \
        in out and "spans {\"real\": 100, \"padded\": 128, \"cached\": 1000}" in out
    # the longest gap: 30 ms from 0.10, its host side named
    at, dur, phases = red["longest_gaps"][0]
    assert (round(at, 4), round(dur, 4)) == (0.1, 0.03)
    assert phases["idle_wait"] == pytest.approx(0.013)


def test_the_first_token_waits_from_the_end_of_its_prefill(hand_ctx, capsys):
    ctx, red = hand_ctx
    (p,) = red["prefills"]
    assert p["program"] == "prefill_ext" and p["real"] == 100
    assert p["module_start"] == pytest.approx(0.13)
    # module ends at 0.16, engine.first_token starts at 0.166
    assert _reader("first_token_after_prefill_ms").read(ctx) \
        == pytest.approx(6.0)
    sr.show(red)
    out = capsys.readouterr().out
    assert "1 dispatch spans, 1 prefill module events" in out
    assert "unattributed" in out and "engine.fetch_chunk" in out
    # fetch_chunk spans end 1 ms after the chunks they waited for
    assert sr.fetch_lags(red) == pytest.approx([0.001, 0.001])


def test_a_prefill_whose_dispatch_the_capture_missed_is_not_paired():
    """The capture opened after a dispatch: its module event runs first and
    belongs to no span; a span lacking its arguments is passed over."""
    host = [("engine.prefill_dispatch", 0.50, 0.01,
             {"request": "b", "program": "prefill", "real": 9, "padded": 64,
              "cached": 0}),
            ("engine.prefill_dispatch", 0.70, 0.01, {"request": "c"}),
            ("engine.first_token", 0.80, 0.0, {"request": "b"})]
    mods = [("jit_prefill(1)", 0.40, 0.05), ("jit_prefill_ext(2)", 0.52, 0.02),
            ("jit_prefill(1)", 0.55, 0.05)]
    (p,) = sr.pair_prefills(host, mods)
    assert (p["request"], p["module_start"]) == ("b", 0.55)
    assert p["first_token_start"] == 0.80


def test_phase_segments_nest_and_partition():
    host = [("engine.step", 0.0, 1.0, {}),
            ("engine.decode_dispatch", 0.2, 0.5, {}),
            ("engine.fetch_chunk", 0.3, 0.2, {}),      # page pressure: nested
            ("engine.prefill_dispatch", 0.25, 0.01, {}),   # not a phase
            ("engine.idle_wait", 1.5, 0.5, {})]
    assert sr.phase_segments(host) == [
        (0.0, 0.2, "other"), (0.2, 0.3, "decode_dispatch"),
        (0.3, 0.5, "fetch_chunk"), (0.5, 0.7, "decode_dispatch"),
        (0.7, 1.0, "other"), (1.5, 2.0, "idle_wait")]
    assert sr.overlap([(0.1, 0.35), (0.9, 1.6)], sr.phase_segments(host)) \
        == pytest.approx({"other": 0.2, "decode_dispatch": 0.1,
                          "fetch_chunk": 0.05, "idle_wait": 0.1})


# --- a capture made here: the spans as the profiler records them -------------

def _capture(path, python_tracer):
    import jax
    import jax.numpy as jnp

    from kukeon_tpu.obs import Registry
    from kukeon_tpu.obs.spans import LoopSpans, span

    spans = LoopSpans(Registry())
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    def loop():
        for i in range(3):
            with spans.span("engine.step"):
                with spans.span("engine.admit", free=1, queued=i):
                    with spans.span("engine.prefill_dispatch",
                                    request=f"t{i}", slot=0) as sp:
                        f(x)
                        sp.set(program="prefill", hit=0, cached=0,
                               real=10 + i, padded=64)
                with spans.span("engine.fetch_first", n=1):
                    f(x).block_until_ready()
                with spans.span("engine.emit", tokens=1):
                    with spans.span("engine.first_token", request=f"t{i}"):
                        pass
            with spans.span("engine.idle_wait"):
                time.sleep(0.002)
        with span("cell.generate") as sp:
            sp.set(request="t9")

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(python_tracer)
    jax.profiler.start_trace(str(path), profiler_options=options)
    try:
        t = threading.Thread(target=loop, name="serving-engine")
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("python_tracer", [False, True])
def test_span_reduce_reads_a_capture_with_or_without_python_frames(
        tmp_path, python_tracer):
    _capture(tmp_path / "cap", python_tracer)
    red = sr.reduce(str(tmp_path / "cap"))
    assert {n: r["count"] for n, r in red["spans"].items()} == {
        "engine.step": 3, "engine.admit": 3, "engine.prefill_dispatch": 3,
        "engine.fetch_first": 3, "engine.emit": 3, "engine.first_token": 3,
        "engine.idle_wait": 3, "cell.generate": 1}
    args = [st for _s, _d, st in red["events"]["engine.prefill_dispatch"]]
    assert args == [{"request": f"t{i}", "slot": 0, "program": "prefill",
                     "hit": 0, "cached": 0, "real": 10 + i, "padded": 64}
                    for i in range(3)]
    assert [st for _s, _d, st in red["events"]["engine.first_token"]] == [
        {"request": f"t{i}"} for i in range(3)]
    assert red["spans"]["engine.idle_wait"]["seconds"] >= 0.006
    # no device plane on the CPU: the host side only, and the device readers
    # find nothing
    assert "idle_s" not in red
    ctx = {"capture": {"rec": {"path": str(tmp_path / "cap")},
                       "metrics_before": {}, "metrics_after": {}}}
    assert _reader("idle_in_host_work_share").read(ctx) is None
    assert _reader("first_token_after_prefill_ms").read(ctx) is None
    assert os.path.exists(tmp_path / "cap" / "span_reduction.json")
    if python_tracer:       # the frames are there, and were passed over
        from jax.profiler import ProfileData

        data = ProfileData.from_file(glob.glob(
            str(tmp_path / "cap" / "**" / "*.xplane.pb"), recursive=True)[0])
        names = {ev.name for p in data.planes if p.name == sr.HOST_PLANE
                 for ln in p.lines for ev in ln.events}
        assert len(names) > len(red["spans"]) + 5


# --- the harness: a traced window of the tiny configuration ------------------

def test_a_traced_run_reports_the_counter_metrics_and_leaves_out_the_rest(
        tmp_path, monkeypatch, capfd):
    """The new entries appended to a temporary copy of the fixture manifest
    (which is not edited). On the CPU the capture holds the spans and no device
    plane: the counter metrics have values, the span metrics are left out, and
    the result line is whole."""
    from benchmark import trace_reduce as tr

    root = tmp_path / "copy"
    shutil.copytree(inproc.FIXTURES, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in _entries():
        bench["per_layer"].append({**m, "workloads": ["tiny.sessions"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    with gzip.open(os.path.join(inproc.FIXTURES, "trace-small.json.gz"),
                   "rt") as f:
        planes = json.load(f)["planes"]
    monkeypatch.setattr(tr, "read_planes", lambda path: planes)
    monkeypatch.setattr(run, "reduce_trace",
                        lambda capture, run_dir: tr.reduce("recorded"))
    monkeypatch.setenv("KUKEON_PROFILE_DIR", str(tmp_path / "profiles"))
    spec = run.load_cell(str(root), "tiny.sessions")
    assert [m["name"] for m in spec["per_layer"]][-len(NEW):] == NEW
    child = inproc.InProcessCell(spec, 41)
    try:
        out = run.drive(child, spec, 41, 4.0, True, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capfd.readouterr().out      # span_reduce.py prints from its process
    assert inproc.sound(out), text
    got = out["metrics"]
    assert set(got) == {"queue_wait_p90_ms", "ttft_p90_ms", "prefill_pad_share",
                        "prefix_token_hit_share", "host_ms_per_step"}
    assert 0 < got["prefill_pad_share"]["value"] < 100
    assert 0 < got["prefix_token_hit_share"]["value"] < 100
    assert 0 < got["host_ms_per_step"]["value"] < 1000
    # the span table of the tiny engine's own capture was printed
    assert "spans: name / count / seconds" in text
    assert "engine.prefill_dispatch" in text and "cell.generate" in text
    assert "no device operation in this capture" in text
    json.dumps(out)
