"""The four readers of a request's time by phase (benchmark/layer_metrics:
request_queued_ms, request_prefill_ms, request_decode_ms_per_token,
decode_stalled_by_prefill_share): over hand-made scrapes and captures each
returns the hand-computed value; over scrapes without the histogram, a flat
window, a program without the span argument or no capture each returns None
and never raises; BENCHMARK.json lists them; and the in-process harness
reports the three histogram ones in a traced run, where they sum to the
engine's own end-to-end mean."""

import gzip
import json
import os
import shutil
import time

import pytest

import inproc
from benchmark import plugins, run, span_reduce as sr, trace_reduce as tr

HISTOGRAM = ["request_queued_ms", "request_prefill_ms",
             "request_decode_ms_per_token"]
NEW = HISTOGRAM + ["decode_stalled_by_prefill_share"]
QUEUE_WAIT = "kukeon_engine_queue_wait_seconds"
TTFT = "kukeon_engine_ttft_seconds"
TOKEN_GAP = "kukeon_engine_inter_token_seconds"
E2E = "kukeon_engine_e2e_seconds"


def _read(name, ctx):
    return plugins.load("layer_metrics", name).read(ctx)


# --- the histogram readers: hand-made scrapes --------------------------------

def _scrape(**observed):
    """{family: (sum, count)} as a parsed scrape holds a histogram."""
    out = {}
    for family, (seconds, count) in observed.items():
        out[family + "_sum"] = [({}, seconds)]
        out[family + "_count"] = [({}, float(count))]
        out[family + "_bucket"] = [({"le": "+Inf"}, float(count))]
    return out


OPEN = _scrape(**{QUEUE_WAIT: (1.0, 10), TTFT: (3.5, 10),
                  TOKEN_GAP: (20.0, 900), E2E: (23.5, 9)})
# in the window four requests leave the queue after 0.8 s in all, four first
# tokens come 2.0 s in all after their submits, 400 token gaps last 6.0 s, and
# four requests end after 8.0 s in the engine in all
CLOSE = _scrape(**{QUEUE_WAIT: (1.8, 14), TTFT: (5.5, 14),
                   TOKEN_GAP: (26.0, 1300), E2E: (31.5, 13)})


def _ctx(**over):
    return {"metrics_open": OPEN, "metrics_close": CLOSE,
            "client": {"latency_mean_ms": 2020.0}, **over}


@pytest.mark.parametrize("name, want", [
    ("request_queued_ms", 200.0),                 # 0.8 s / 4
    ("request_prefill_ms", 300.0),                # 2.0 s / 4 - 0.8 s / 4
    ("request_decode_ms_per_token", 15.0),        # 6.0 s / 400
])
def test_a_histogram_reader_gives_the_hand_computed_mean(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


def test_the_three_with_the_token_gaps_sum_to_the_engines_own_mean(capsys):
    got = {n: _read(n, _ctx()) for n in HISTOGRAM}
    gaps_a_request = 400 / 4
    total = (got["request_queued_ms"] + got["request_prefill_ms"]
             + got["request_decode_ms_per_token"] * gaps_a_request)
    assert total == pytest.approx(8.0 / 4 * 1e3)
    # the decode reader prints the same sum beside the client's mean latency
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("request phases:"))
    assert "queued 200.0 + prefill 300.0 + decode 15.000 x 100.00 token gaps an answer" in line
    assert "= 2000.0 ms" in line and "submit -> ended 2000.0 ms" in line
    assert "latency_mean_ms 2020.0 (-20.0 ms, -0.99%)" in line


def test_the_sum_is_printed_without_the_clients_side_too(capsys):
    ctx = _ctx()
    del ctx["client"]
    assert _read("request_decode_ms_per_token", ctx) == pytest.approx(15.0)
    line = capsys.readouterr().out.strip()
    assert line.endswith("submit -> ended 2000.0 ms")


def test_the_sum_takes_the_token_gaps_of_the_answers_sent_in_the_window(
        capsys):
    """Three answers of 81, 101 and 121 tokens sent in the window; one sent
    before it and one that failed are not the client's mean's either."""
    def rec(tokens, in_window=True, ok=True):
        return {"in_window": in_window, "ok": ok,
                "token_times": [0.0] * tokens}
    records = [rec(81), rec(101), rec(121), rec(900, in_window=False),
               rec(1, ok=False)]
    _read("request_decode_ms_per_token", _ctx(records=records))
    line = capsys.readouterr().out
    assert "decode 15.000 x 100.00 token gaps an answer = 2000.0 ms" in line


def _without(scrape, family):
    return {k: v for k, v in scrape.items() if not k.startswith(family)}


FAMILY = {"request_queued_ms": QUEUE_WAIT, "request_prefill_ms": TTFT,
          "request_decode_ms_per_token": TOKEN_GAP}


@pytest.mark.parametrize("name", HISTOGRAM)
@pytest.mark.parametrize("over", [
    pytest.param("its family", id="scrapes without its histogram"),
    pytest.param({"metrics_open": {}, "metrics_close": {}},
                 id="empty scrapes"),
    pytest.param({"metrics_open": CLOSE, "metrics_close": CLOSE},
                 id="it observed nothing in the window"),
    pytest.param({"metrics_open": CLOSE, "metrics_close": OPEN},
                 id="the cell restarted between the scrapes"),
])
def test_a_histogram_reader_gives_none_and_does_not_raise(name, over):
    if over == "its family":
        over = {"metrics_open": _without(OPEN, FAMILY[name]),
                "metrics_close": _without(CLOSE, FAMILY[name])}
    assert _read(name, _ctx(**over)) is None


def test_prefill_is_none_without_the_queue_wait_it_is_taken_from():
    over = {"metrics_open": _without(OPEN, QUEUE_WAIT),
            "metrics_close": _without(CLOSE, QUEUE_WAIT)}
    assert _read("request_prefill_ms", _ctx(**over)) is None
    assert _read("request_decode_ms_per_token", _ctx(**over)) == 15.0


def test_prefill_is_none_where_the_two_means_cross():
    """A first token 0.1 s after its submit beside a queue wait of 0.2 s:
    the two histograms saw different requests, and no time is negative."""
    close = {**CLOSE, **_scrape(**{TTFT: (3.9, 14)})}
    assert _read("request_prefill_ms", _ctx(metrics_close=close)) is None


# --- the span reader: a hand-made capture ------------------------------------

def _hand_capture(decoding=True, active=True):
    """Device 0: a chunk of two slots (0.00-0.04), its successor (0.04-0.08),
    a prefill dispatched beside those two slots (0.08-0.20), a prefill_ext
    dispatched beside three (0.20-0.23), then chunks of four slots (0.23-0.28
    and 0.28-0.33; the last one's fetch is not in the capture).

    S = 0.12 x 2 + 0.03 x 3 = 0.33 slot-seconds behind a prefill;
    D = 0.04 x 2 + 0.04 x 2 + 0.05 x 4 = 0.36 slot-seconds decoding."""
    def sp(name, a, b, **st):
        return (name, a, b - a, st)

    def dec(n):
        return {"decoding": n} if decoding else {}

    def act(n):
        return {"k": 4, **({"active": n} if active else {})}

    host = [
        # a fetch whose chunk ran before the capture began: pairs with none
        sp("engine.fetch_chunk", 0.001, 0.002, **act(2)),
        sp("engine.fetch_chunk", 0.010, 0.0405, **act(2)),
        sp("engine.prefill_dispatch", 0.050, 0.055, request="r1", slot=2,
           program="prefill", hit=0, cached=0, real=900, padded=1024,
           **dec(2)),
        sp("engine.prefill_dispatch", 0.056, 0.060, request="r2", slot=3,
           program="prefill_ext", hit=1, cached=512, real=100, padded=128,
           **dec(3)),
        # the host waited for both prefills' first tokens meanwhile, so this
        # fetch returns long after its chunk ended (0.08) and while the next
        # chunk (0.23-0.28) is still running
        sp("engine.fetch_chunk", 0.231, 0.232, **act(2)),
        sp("engine.fetch_chunk", 0.240, 0.2805, **act(4)),
    ]
    mods = [("jit_decode_chunk_fn(1)", 0.00, 0.04),
            ("jit_decode_chunk_fn(1)", 0.04, 0.04),
            ("jit_prefill(2)", 0.08, 0.12),
            ("jit_prefill_ext(3)", 0.20, 0.03),
            ("jit_decode_chunk_fn(1)", 0.23, 0.05),
            ("jit_decode_chunk_fn(1)", 0.28, 0.05)]
    # the device's own clock starts at 5.0 in this capture
    host = [(n, s + 5.0, d, st) for n, s, d, st in host]
    mods = [(n, s + 5.0, d) for n, s, d in mods]
    host.append(("engine.step", 5.0, 0.33, {}))
    return {"host": host, "ops": [(s, d) for _n, s, d in mods],
            "modules": mods, "window": (5.0, 5.33)}


def _span_ctx(tmp_path, monkeypatch, cap):
    monkeypatch.setattr(sr, "read_capture", lambda _path: cap)
    d = tmp_path / "capture"
    d.mkdir()
    (d / "span_reduction.json").write_text(json.dumps(sr.reduce(str(d))))
    modules = {}
    lo = cap["window"][0] if cap["window"] else 0.0
    for name, s, dur in cap["modules"]:
        base, program = tr.module_of(name)
        m = modules.setdefault(base, {"count": 0, "seconds": 0.0,
                                      "events": []})
        m["count"] += 1
        m["seconds"] += dur
        m["events"].append([s - lo, dur, program])
    return {"capture": {"rec": {"path": str(d)}, "metrics_before": {},
                        "metrics_after": {}},
            "trace": {"devices": [{"modules": modules}]}}


def test_stalled_share_is_prefill_slot_time_over_all_decoding_slot_time(
        tmp_path, monkeypatch):
    ctx = _span_ctx(tmp_path, monkeypatch, _hand_capture())
    assert _read("decode_stalled_by_prefill_share", ctx) \
        == pytest.approx(100 * 0.33 / (0.33 + 0.36))


def test_stalled_share_is_none_where_no_prefill_lies_whole_in_the_capture(
        tmp_path, monkeypatch):
    cap = _hand_capture()
    cap["host"] = [e for e in cap["host"]
                   if e[0] != "engine.prefill_dispatch"]
    assert _read("decode_stalled_by_prefill_share",
                 _span_ctx(tmp_path, monkeypatch, cap)) is None


def test_stalled_share_is_zero_where_the_prefills_ran_beside_no_decoding_slot(
        tmp_path, monkeypatch):
    cap = _hand_capture()
    for _n, _s, _d, st in cap["host"]:
        if "decoding" in st:
            st["decoding"] = 0
    assert _read("decode_stalled_by_prefill_share",
                 _span_ctx(tmp_path, monkeypatch, cap)) == 0.0


@pytest.mark.parametrize("cap", [
    pytest.param(_hand_capture(decoding=False), id="prefills lack decoding"),
    pytest.param(_hand_capture(active=False), id="chunks lack active"),
    pytest.param(_hand_capture(decoding=False, active=False),
                 id="the parent's spans"),
    pytest.param({"host": [], "ops": [(0.0, 0.1)],
                  "modules": [("jit_decode_chunk_fn(1)", 0.0, 0.1)],
                  "window": (0.0, 1.0)}, id="no span of the program's"),
    pytest.param({**_hand_capture(), "ops": [], "window": None},
                 id="no device operation"),
])
def test_stalled_share_is_none_where_an_argument_or_the_device_is_missing(
        tmp_path, monkeypatch, cap):
    assert _read("decode_stalled_by_prefill_share",
                 _span_ctx(tmp_path, monkeypatch, cap)) is None


def test_stalled_share_is_none_without_a_capture():
    assert _read("decode_stalled_by_prefill_share", {"capture": {}}) is None


# --- the manifest, and a run of the tiny cell --------------------------------

def _entries():
    with open(os.path.join(plugins.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m for m in bench["per_layer"] if m["name"] in NEW]


def test_benchmark_json_lists_them_last_and_the_span_one_where_it_reads():
    bench, entries = _entries()
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW
    cells = [w["name"] for w in bench["workloads"]]
    for m in entries:
        assert m["better"] == "lower" and m["layer"] == "engine"
        assert m["moves"] == "latency_mean_ms"
        assert os.path.exists(os.path.join(
            plugins.HERE, "layer_metrics", m["name"] + ".py"))
        if m["name"] in HISTOGRAM:
            assert "workloads" not in m and m["source"] == "program_counter"
    # a 3 s capture of deepseek's cell holds one prefill or none
    assert entries[-1]["workloads"] == [
        c for c in cells if not c.startswith("deepseek")]
    for cell in cells:
        spec = run.load_cell(plugins.REPO, cell)
        assert set(HISTOGRAM) <= {m["name"] for m in spec["per_layer"]}, cell


def test_a_traced_run_of_the_tiny_cell_reports_the_three_and_they_add_up(
        tmp_path, monkeypatch, capfd):
    """The entries appended to a temporary copy of the fixture manifest. On
    the CPU the capture holds no device plane, so the span reader is left out;
    the three histogram readers read what the engine observed in the window,
    and with the token gaps a request they come to the engine's own mean
    submit -> ended there, which the run prints beside the client's."""
    root = tmp_path / "copy"
    shutil.copytree(inproc.FIXTURES, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in _entries()[1]:
        bench["per_layer"].append({**m, "moves": "ttft_mean_ms",
                                   "workloads": ["tiny.sessions"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with gzip.open(os.path.join(inproc.FIXTURES, "trace-small.json.gz"),
                   "rt") as f:
        planes = json.load(f)["planes"]
    monkeypatch.setattr(tr, "read_planes", lambda path: planes)
    monkeypatch.setattr(run, "reduce_trace",
                        lambda capture, run_dir: tr.reduce("recorded"))
    monkeypatch.setenv("KUKEON_PROFILE_DIR", str(tmp_path / "profiles"))
    spec = run.load_cell(str(root), "tiny.sessions")
    child = inproc.InProcessCell(spec, 47)
    try:
        out = run.drive(child, spec, 47, 4.0, True, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capfd.readouterr().out
    assert inproc.sound(out), text
    got = {n: out["metrics"][n]["value"] for n in HISTOGRAM}
    assert "decode_stalled_by_prefill_share" not in out["metrics"]
    assert all(v > 0 for v in got.values()), got
    line = next(ln for ln in text.splitlines()
                if ln.startswith("request phases:"))
    assert "the client's latency_mean_ms" in line
    total = float(line.split(" = ")[1].split(" ms")[0])
    ended = float(line.split("submit -> ended ")[1].split(" ms")[0])
    # The engine's clock runs on past the last token, over the slot's release
    # (milliseconds of a 27 ms request here, of seconds on the chip), and each
    # histogram saw the requests whose wait ended inside these 4 s.
    assert 0.5 * ended < total < 1.25 * ended, line
