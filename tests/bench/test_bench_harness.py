"""Launcher, generators, client timing, the reference check and the metric
arithmetic driven in one process against a tiny cell on the CPU: everything of
a run but the look for a chip (tests/bench/inproc.py stands in for the child
process)."""

import copy
import json
import os
import shutil
import time

import pytest

import inproc
from benchmark import plugins, run

E2E = {"ttft_mean_ms", "tpot_mean_ms", "slo_share", "out_tok_per_s", "setup_s"}


def _drive(workload, seed, tmp_path, seconds=3.0, controls=(), root=None,
           trace=False):
    spec = run.load_cell(root or inproc.FIXTURES, workload)
    child = inproc.InProcessCell(spec, seed % run.SEED_MOD)
    try:
        return run.drive(child, spec, seed, seconds, trace, str(tmp_path),
                         time.monotonic(), controls=controls), spec
    finally:
        child.close()


@pytest.mark.parametrize("workload", ["tiny.independent", "tiny.sessions"])
def test_a_sound_run_is_correct_and_reports_every_end_to_end_metric(
        workload, tmp_path, capsys):
    out, spec = _drive(workload, 3000000011, tmp_path)
    text = capsys.readouterr().out
    assert out["correct"] is True, text
    assert out["failed"] == 0 and out["attempted"] >= 10
    assert set(out["metrics"]) == E2E
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert out["metrics"]["slo_share"]["value"] == 100.0
    assert out["device"]["platform"] == "cpu"       # never under a TPU's name
    # every number compared is printed beside its limit
    assert "gap_max" in text and "(limit" in text
    assert "compiles in the window: 0 (limit 0)" in text
    assert "generator lateness: p50" in text
    assert '"prefill_buckets"' in text and '"weights_seed"' in text
    json.dumps(out)


def test_a_four_chip_cell_on_four_host_devices(tmp_path, capsys):
    """The rehearsal of a tensor-parallel cell: four forced host devices, KV
    sharded over the KV heads, the reference spread over the same devices."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices")
    out, _spec = _drive("tiny-tp4.independent", 17, tmp_path)
    text = capsys.readouterr().out
    assert out["correct"] is True, text
    assert '"mesh_chips": 4' in text and out["failed"] == 0


def test_sessions_hit_the_prefix_cache(tmp_path):
    spec = run.load_cell(inproc.FIXTURES, "tiny.sessions")
    child = inproc.InProcessCell(spec, 5)
    try:
        out = run.drive(child, spec, 5, 3.0, False, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    # follow-up turns found their session's prefix: the engine counted hits
    assert out["correct"] is True
    assert child.engine.prefix_hits >= 5
    assert child.engine.prefix_hits > child.engine.prefix_misses / 2


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch, capsys):
    from kukeon_tpu.serving import engine as eng

    emit = eng.ServingEngine._emit

    def wrong(self, req, token):
        return emit(self, req, (int(token) + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(eng.ServingEngine, "_emit", wrong)
    out, _spec = _drive("tiny.independent", 11, tmp_path)
    assert out["correct"] is False
    assert '"reference": false' in capsys.readouterr().out


def test_a_request_cut_short_is_not_correct(tmp_path, monkeypatch):
    from benchmark import loadgen

    post = loadgen.post_generate

    def short(port, request, timeout_s):
        res = post(port, request, timeout_s)
        if request.get("id") == "r3":
            res["tokens"] = res["tokens"][:-1]
        return res

    monkeypatch.setattr(loadgen, "post_generate", short)
    out, _spec = _drive("tiny.independent", 12, tmp_path)
    assert out["correct"] is False and out["failed"] == 1


def test_the_lower_precision_control_fails_the_limits(tmp_path, capsys):
    """The control: the reference in the precision below the configuration's,
    put in the program's place over the same prompts and served tokens, has to
    come out above a limit (here at a size a test run can hold)."""
    out, spec = _drive("tiny.independent", 13, tmp_path, seconds=5.0,
                       controls=("a8", "w4"))
    assert out["correct"] is True
    limits = spec["config"]["check"]["limits"]
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("control ")]
    assert len(lines) == 2
    for ln in lines:
        got = json.loads(ln.split(": ", 1)[1])
        assert any(got[k] > limits[k] for k in limits), ln


def test_the_launcher_refuses_a_lever_from_the_environment(monkeypatch):
    spec = run.load_cell(inproc.FIXTURES, "tiny.independent")
    monkeypatch.setenv("KUKEON_INT8_PALLAS", "1")
    with pytest.raises(SystemExit, match="KUKEON_INT8_PALLAS"):
        inproc.InProcessCell(spec, 1).start()


def test_the_launcher_refuses_a_tune_file(monkeypatch, tmp_path):
    from kukeon_tpu.serving import tuning

    spec = run.load_cell(inproc.FIXTURES, "tiny.independent")
    monkeypatch.setattr(tuning, "load", lambda *a, **k: tuning.ServingTune())
    with pytest.raises(SystemExit, match="tune"):
        inproc.InProcessCell(spec, 1).start()


def test_new_cells_metrics_and_files_are_added_without_editing_any(
        tmp_path, capsys):
    """A throw-away configuration, mix, generator, per-layer metric and cell,
    added to a temporary copy as new files plus entries of BENCHMARK.json."""
    root = tmp_path / "copy"
    shutil.copytree(inproc.FIXTURES, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    pkg = root / "bench"
    cfg = json.loads((pkg / "configs" / "tiny-dense.json").read_text())
    cfg.update(name="tiny-wide", num_hidden_layers=3)
    (pkg / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    (pkg / "generators").mkdir()
    (pkg / "generators" / "burst.py").write_text(
        "class Generator:\n"
        "    def __init__(self, params, seed, vocab, seconds):\n"
        "        self.n, self.vocab = params['n'], vocab\n"
        "    def arrivals(self):\n"
        "        return [{'id': f'b{i}', 'due': 0.15 * i, 'prefix_id': None,\n"
        "                 'prompt': [(i * 7 + j) % self.vocab for j in range(30)],\n"
        "                 'max_new_tokens': 5, 'new_tokens': 30}\n"
        "                for i in range(self.n)]\n"
        "    def on_complete(self, request, tokens, done_at):\n"
        "        return []\n")
    mix = json.loads((pkg / "traffic" / "tiny-independent.json").read_text())
    mix.update(generator="burst", params={"n": 12})
    (pkg / "traffic" / "tiny-burst.json").write_text(json.dumps(mix))
    (pkg / "layer_metrics").mkdir()
    (pkg / "layer_metrics" / "tokens_counted.py").write_text(
        "from benchmark import stats\n"
        "def read(ctx):\n"
        "    return stats.delta(ctx['metrics_open'], ctx['metrics_close'],\n"
        "                       'kukeon_engine_tokens_total')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide.burst", "config": "tiny-wide",
                               "traffic": "tiny-burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "tokens_counted", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "out_tok_per_s", "workloads": ["wide.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out, spec = _drive("wide.burst", 21, tmp_path, root=str(root))
    assert out["correct"] is True and out["attempted"] == 12
    assert [m["name"] for m in spec["per_layer"]] == [
        "queue_wait_p90_ms", "ttft_p90_ms", "tokens_counted"]
    reader = plugins.load("layer_metrics", "tokens_counted", spec["pkg_dir"])
    assert reader.read({"metrics_open": {}, "metrics_close": {
        "kukeon_engine_tokens_total": [({}, 60.0)]}}) == 60.0
    # the old cell still loads, and no file that was there changed
    assert run.load_cell(str(root), "tiny.sessions")["per_layer"][0]["name"] \
        == "queue_wait_p90_ms"
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(
        tmp_path, monkeypatch, capsys):
    """The traced path of a run on the CPU: the capture is asked of the cell's
    own /v1/profile; the reduction of the trace recorded on the chip stands in
    for the CPU capture's (which holds no device plane)."""
    import gzip

    from benchmark import trace_reduce as tr

    with gzip.open(os.path.join(inproc.FIXTURES, "trace-small.json.gz"),
                   "rt") as f:
        planes = json.load(f)["planes"]
    monkeypatch.setattr(tr, "read_planes", lambda path: planes)
    asked = {}

    def fake_reduce(capture, run_dir):
        asked.update(capture)
        return tr.reduce("recorded")

    monkeypatch.setattr(run, "reduce_trace", fake_reduce)
    monkeypatch.setenv("KUKEON_PROFILE_DIR", str(tmp_path / "profiles"))
    out, _spec = _drive("tiny.sessions", 31, tmp_path, seconds=4.0, trace=True)
    assert asked["status"] == 200 and asked["rec"]["state"] == "running"
    assert "metrics_before" in asked and "metrics_after" in asked
    assert out["correct"] is True
    assert set(out["metrics"]) == {"queue_wait_p90_ms", "ttft_p90_ms"}
    assert not set(out["metrics"]) & E2E                    # no e2e name
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) == 10
    assert 1 <= len(out["breakdown"]["idle_gaps"]) <= 10
    json.dumps(out)
