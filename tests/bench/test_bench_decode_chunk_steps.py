"""``decode_chunk_steps_mean`` (benchmark/layer_metrics): the mean of ``k`` over
the ``engine.fetch_chunk`` events span_reduce.py keeps. Over fixture reductions
it gives the hand-computed mean; where the capture holds no such event, an event
lacks ``k``, or there is no reduction, it gives None and never raises; and
BENCHMARK.json lists it for every cell, as the engine's."""

import gzip
import json
import os
import shutil
import time

import pytest

import inproc
from benchmark import plugins, run, span_reduce as sr, trace_reduce as tr

NAME = "decode_chunk_steps_mean"


def _read(ctx):
    return plugins.load("layer_metrics", NAME).read(ctx)


def _step(at, k):
    """One engine step as the capture holds it: its decode dispatch, then the
    fetch of the PREVIOUS chunk's block, whose length the fetch span names."""
    args = {} if k is None else {"k": k}
    return [("engine.step", at, 0.09, {}),
            ("engine.decode_dispatch", at + 0.001, 0.002,
             {"k": 4, "active": 2, "live_rows": 900}),
            ("engine.fetch_chunk", at + 0.003, 0.08, args),
            ("engine.emit", at + 0.083, 0.004, {"tokens": 8})]


def _ctx(tmp_path, monkeypatch, host):
    """A run's context over span_reduce.reduce() of a capture whose host side
    is ``host`` (one decode module event on device 0)."""
    monkeypatch.setattr(sr, "read_capture", lambda _path: {
        "host": host, "ops": [(0.0, 0.05)],
        "modules": [("jit_decode_chunk_fn(1)", 0.0, 0.05)],
        "window": (0.0, 1.0)})
    cap = tmp_path / "capture"
    cap.mkdir()
    (cap / "span_reduction.json").write_text(json.dumps(sr.reduce(str(cap))))
    return {"capture": {"rec": {"path": str(cap)}, "metrics_before": {},
                        "metrics_after": {}}}


@pytest.mark.parametrize("ks, want", [
    ([16, 16, 16], 16.0),             # the parent: every chunk whole
    ([4, 4, 16, 4], 7.0),             # mixed: the mean, not the mode
    ([4], 4.0),
    ([1, 4, 16, 16, 4, 4, 4, 4], 53 / 8),
])
def test_mean_of_k_over_the_fetched_chunks(tmp_path, monkeypatch, ks, want):
    host = [e for i, k in enumerate(ks) for e in _step(0.1 * i, k)]
    assert _read(_ctx(tmp_path, monkeypatch, host)) == pytest.approx(want)


@pytest.mark.parametrize("host", [
    pytest.param([], id="no span of the program's"),
    pytest.param([("engine.step", 0.0, 0.01, {}),
                  ("engine.admit", 0.0, 0.001, {"free": 8, "queued": 0}),
                  ("engine.idle_wait", 0.02, 0.5, {})],
                 id="spans, none of them a fetch_chunk"),
    pytest.param(_step(0.0, 4) + _step(0.1, None),
                 id="a fetch_chunk without k"),
    pytest.param(_step(0.0, "four"), id="a k that is no number"),
])
def test_none_and_no_exception_where_there_is_nothing_to_read(
        tmp_path, monkeypatch, host):
    assert _read(_ctx(tmp_path, monkeypatch, host)) is None


@pytest.mark.parametrize("capture", [
    pytest.param({}, id="no capture"),
    pytest.param({"rec": {"path": "none"}, "metrics_before": {},
                  "metrics_after": {}},
                 id="a directory span_reduce.py exits non-zero over"),
])
def test_none_where_there_is_no_reduction(capture, tmp_path):
    if "rec" in capture:
        capture = {**capture, "rec": {"path": str(tmp_path / "none")}}
    ctx = {"capture": capture}
    assert _read(ctx) is None
    assert ctx["_spans"] is None      # answered once, not tried again


def _entry():
    with open(os.path.join(plugins.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, next(m for m in bench["per_layer"] if m["name"] == NAME)


def test_benchmark_json_lists_it_for_every_cell_as_the_engines():
    bench, entry = _entry()
    assert entry == {"name": NAME, "unit": "steps", "better": "lower",
                     "source": "program_span", "layer": "engine",
                     "moves": "latency_mean_ms"}
    # no list: every cell reports it, and every cell reports what it moves
    held = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert "workloads" not in held
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}
    for w in bench["workloads"]:
        spec = run.load_cell(plugins.REPO, w["name"])
        assert NAME in [m["name"] for m in spec["per_layer"]], w["name"]
        assert entry["moves"] in [m["name"] for m in spec["end_to_end"]]
    assert os.path.exists(os.path.join(plugins.HERE, "layer_metrics",
                                       NAME + ".py"))


def test_a_traced_run_of_the_tiny_cell_reports_it_from_the_engines_own_spans(
        tmp_path, monkeypatch, capfd):
    """The entry appended to a temporary copy of the fixture manifest. The
    capture made on the CPU holds the engine's spans, so the reader has real
    ``engine.fetch_chunk`` events: a mean of lengths the engine can dispatch,
    and under 16 since the tiny cell's four slots are not all seated all the
    time."""
    root = tmp_path / "copy"
    shutil.copytree(inproc.FIXTURES, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    _bench, entry = _entry()
    bench["per_layer"].append({**entry, "moves": "ttft_mean_ms",
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
    child = inproc.InProcessCell(spec, 43)
    try:
        out = run.drive(child, spec, 43, 4.0, True, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capfd.readouterr().out
    assert inproc.sound(out), text
    got = out["metrics"][NAME]
    assert got["unit"] == "steps" and 1.0 <= got["value"] < 16.0, got
