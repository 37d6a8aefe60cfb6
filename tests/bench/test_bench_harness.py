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
LATENESS = inproc.LATENESS
SECOND_FAMILY = os.path.join(inproc.FIXTURES, "second-family")
_sound = inproc.sound


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
    captured = capsys.readouterr()
    text = captured.out
    assert _sound(out), text
    assert out["correct"] is all(out["checks"].values())
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
    # ... in the result's last key and as the last lines on standard error
    assert list(out)[-1] == "compared"
    limits = spec["config"]["check"]["limits"]
    assert set(out["compared"]) == set(limits) | {
        "compiles_in_window", "failed_requests", "lateness_p50_ms",
        "repeats_differing"}
    for name, c in out["compared"].items():
        assert c["limit"] == limits.get(name, c["limit"])
        assert (c["value"] <= c["limit"]) or name == "lateness_p50_ms"
    last = captured.err.strip().splitlines()[-len(out["compared"]):]
    assert [ln.split()[1] for ln in last] == list(out["compared"])
    assert all(ln.startswith("compared: ") and "(limit " in ln for ln in last)
    # what the reference found on the device when it started
    assert "freed for the reference:" in text
    json.dumps(out)


def test_a_four_chip_cell_on_four_host_devices(tmp_path, capsys):
    """The rehearsal of a tensor-parallel cell: four forced host devices, KV
    sharded over the KV heads, the reference spread over the same devices."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices")
    out, _spec = _drive("tiny-tp4.independent", 17, tmp_path)
    text = capsys.readouterr().out
    assert _sound(out), text
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
    assert _sound(out)
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
    assert out["correct"] is False and out["checks"]["reference"] is False
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
    assert out["checks"]["every_answer_whole"] is False
    assert out["compared"]["failed_requests"] == {"value": 1, "limit": 0}


def test_the_lower_precision_control_fails_the_limits(tmp_path, capsys):
    """The control: the reference in the precision below the configuration's,
    put in the program's place over the same prompts and served tokens, has to
    come out above a limit (here at a size a test run can hold)."""
    out, spec = _drive("tiny.independent", 13, tmp_path, seconds=5.0,
                       controls=("a8", "w4"))
    assert _sound(out)
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


def _copy_of_the_fixtures(tmp_path):
    """(root, the bytes of every file that is there)."""
    root = tmp_path / "copy"
    shutil.copytree(inproc.FIXTURES, root)
    return root, {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def _nothing_that_was_there_changed(before):
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data


def _add_entries(root, **entries):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, new in entries.items():
        bench[key].extend(new)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cells_metrics_and_files_are_added_without_editing_any(
        tmp_path, capsys):
    """A throw-away configuration, mix, generator, per-layer metric and cell,
    added to a temporary copy as new files plus entries of BENCHMARK.json."""
    root, before = _copy_of_the_fixtures(tmp_path)
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
    _add_entries(root, configs=[{
        "name": "tiny-wide", "source": "test",
        "file": "bench/configs/tiny-wide.json", "reduced": [], "why": "test"}],
        workloads=[{"name": "wide.burst", "config": "tiny-wide",
                    "traffic": "tiny-burst", "chips": 1, "why": "test"}],
        per_layer=[{
            "name": "tokens_counted", "unit": "tokens", "better": "higher",
            "source": "program_counter", "layer": "engine",
            "moves": "out_tok_per_s", "workloads": ["wide.burst"]}])

    out, spec = _drive("wide.burst", 21, tmp_path, root=str(root))
    assert _sound(out) and out["attempted"] == 12
    assert [m["name"] for m in spec["per_layer"]] == [
        "queue_wait_p90_ms", "ttft_p90_ms", "tokens_counted"]
    reader = plugins.load("layer_metrics", "tokens_counted", spec["pkg_dir"])
    assert reader.read({"metrics_open": {}, "metrics_close": {
        "kukeon_engine_tokens_total": [({}, 60.0)]}}) == 60.0
    # the old cell still loads, and no file that was there changed
    assert run.load_cell(str(root), "tiny.sessions")["per_layer"][0]["name"] \
        == "queue_wait_p90_ms"
    _nothing_that_was_there_changed(before)


def _add_the_second_family(root):
    """``launchers/<family>.py``, ``reference/<family>.py`` and a
    configuration with keys a dense file has not, as new files."""
    for kind in ("launchers", "reference", "configs"):
        shutil.copytree(os.path.join(SECOND_FAMILY, kind),
                        root / "bench" / kind, dirs_exist_ok=True)
    _add_entries(root, configs=[{
        "name": "tiny-moe", "source": "test",
        "file": "bench/configs/tiny-moe.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "moe.independent", "config": "tiny-moe",
                    "traffic": "tiny-independent", "chips": 1,
                    "why": "test"}])


@pytest.mark.parametrize("altered", [False, True])
def test_a_second_family_is_launched_served_and_checked_as_new_files_only(
        altered, tmp_path, monkeypatch, capsys):
    """The seam the next architecture comes through: a family the dense
    launcher cannot build (an expert layer, the program's own switch to its
    second family) brings a launcher and a reference of its own, and nothing
    that was there changes. Its sound run is correct; a token altered where it
    is produced is not."""
    from kukeon_tpu.models import moe
    from kukeon_tpu.runtime import serving_cell as sc
    from kukeon_tpu.serving import engine as eng

    root, before = _copy_of_the_fixtures(tmp_path)
    assert not (root / "bench" / "launchers").exists()
    _add_the_second_family(root)
    monkeypatch.setattr(sc, "MODELS", dict(sc.MODELS))
    monkeypatch.setattr(sc, "MOE_MODELS", set(sc.MOE_MODELS))
    if altered:
        emit = eng.ServingEngine._emit
        monkeypatch.setattr(
            eng.ServingEngine, "_emit", lambda self, req, token: emit(
                self, req, (int(token) + 1) % self.cfg.vocab_size))
    spec = run.load_cell(str(root), "moe.independent")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capsys.readouterr().out
    assert isinstance(child.engine.cfg, moe.MoEConfig)      # not a dense one
    assert "tiny-moe" in sc.MOE_MODELS
    assert out["attempted"] >= 10 and out["failed"] == 0
    if altered:
        assert out["correct"] is False
        assert out["checks"]["reference"] is False, text
        assert out["compared"]["gap_mean"]["value"] > 0.1
    else:
        assert _sound(out), text
        assert out["compared"]["gap_max"]["value"] < 0.01
    # the dense cells still find their launcher under benchmark/
    assert run.load_cell(str(root), "tiny.sessions")["config"]["reference"] \
        == "dense_gqa"
    _nothing_that_was_there_changed(before)


def test_a_late_generator_is_not_correct(tmp_path, capsys):
    """The one check the other tests leave out, held by a generator that is
    late by construction: each answer brings two follow-ups that were due
    60 ms before it came, so most requests leave late whatever the host."""
    root, _before = _copy_of_the_fixtures(tmp_path)
    pkg = root / "bench"
    (pkg / "generators").mkdir()
    (pkg / "generators" / "late.py").write_text(
        "class Generator:\n"
        "    def __init__(self, params, seed, vocab, seconds):\n"
        "        self.vocab = vocab\n"
        "    def _one(self, rid, due):\n"
        "        return {'id': rid, 'due': due, 'prefix_id': None,\n"
        "                'prompt': [(len(rid) * 7 + j) % self.vocab\n"
        "                           for j in range(30)],\n"
        "                'max_new_tokens': 3, 'new_tokens': 30}\n"
        "    def arrivals(self):\n"
        "        return [self._one(f'a{i}', 0.3 + 0.2 * i) for i in range(5)]\n"
        "    def on_complete(self, request, tokens, done_at):\n"
        "        if not request['id'].startswith('a'):\n"
        "            return []\n"
        "        return [self._one(f\"late{k}{request['id']}\", done_at - 0.06)\n"
        "                for k in range(2)]\n")
    mix = json.loads((pkg / "traffic" / "tiny-independent.json").read_text())
    mix.update(generator="late", params={})
    (pkg / "traffic" / "tiny-late.json").write_text(json.dumps(mix))
    _add_entries(root, workloads=[{
        "name": "tiny.late", "config": "tiny-dense", "traffic": "tiny-late",
        "chips": 1, "why": "test"}])
    out, _spec = _drive("tiny.late", 27, tmp_path, root=str(root))
    assert out["attempted"] == 15 and out["failed"] == 0
    assert out["checks"][LATENESS] is False and out["correct"] is False
    assert out["compared"]["lateness_p50_ms"]["value"] >= 60.0
    assert out["compared"]["lateness_p50_ms"]["limit"] == 5.0
    assert _sound(out)      # nothing else is at fault
    assert '"lateness_p50_under_5ms": false' in capsys.readouterr().out


def test_stop_and_free_leaves_no_live_device_array():
    """Whatever the program holds on the device (weights, decode state, the
    prefix store's blocks, anything a later family adds) is deleted before the
    reference runs, and no array of anybody else's is touched."""
    import jax
    import jax.numpy as jnp

    from benchmark import loadgen

    others = jnp.arange(5.0)
    before = jax.live_arrays()              # held, so that no id is reused
    spec = run.load_cell(inproc.FIXTURES, "tiny.sessions")
    child = inproc.InProcessCell(spec, 9)
    try:
        port = child.start()["port"]
        res = loadgen.post_generate(port, {
            "prompt": list(range(40)), "max_new_tokens": 4,
            "prefix_id": "kept-in-the-prefix-store"}, timeout_s=120.0)
        assert res["status"] == 200 and len(res["tokens"]) == 4
        assert len(child.engine._prefix_cache) == 1
        assert len(jax.live_arrays()) - len(before) > 10
        freed = child.host.stop_and_free()
    finally:
        child.close()
    mine = {id(a) for a in before}
    assert [a.shape for a in jax.live_arrays() if id(a) not in mine] == []
    assert freed["freed_arrays"] > 10
    # at least the int8 weights: two layers of 128 x (128+64+64+128+3*256)
    assert freed["freed_bytes"] > 2 * 128 * 1152
    assert freed["bytes_in_use_under_reference"] == 0      # the CPU reports none
    assert float(others.sum()) == 10.0 and not others.is_deleted()


@pytest.mark.parametrize("family", ["dense_gqa", "moe_softmax_topk"])
def test_rehearse_compile_builds_its_engine_through_the_launcher(
        family, tmp_path):
    """Compile-only sizes exist for a family before its first chip run: the
    abstract engine comes from ``launchers/<family>.py`` (here over host
    devices; the TPU compiler is not asked)."""
    import jax

    from benchmark import rehearse_compile
    from kukeon_tpu.models import llama, moe
    from kukeon_tpu.parallel import make_mesh

    root, _before = _copy_of_the_fixtures(tmp_path)
    _add_the_second_family(root)
    name = {"dense_gqa": "tiny-dense", "moe_softmax_topk": "tiny-moe"}[family]
    config = json.loads(
        (root / "bench" / "configs" / f"{name}.json").read_text())
    assert config["reference"] == family
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    cfg, eng = rehearse_compile.abstract_engine(config, mesh,
                                                str(root / "bench"))
    want = {"dense_gqa": (llama.LlamaConfig, llama.forward),
            "moe_softmax_topk": (moe.MoEConfig, moe.forward)}[family]
    assert type(cfg) is want[0] and eng._forward is want[1]
    assert ("router" in eng._abstract_params["layers"]) \
        == (family == "moe_softmax_topk")
    # the levers are the configuration file's
    s = config["serving"]
    assert (eng.num_slots, eng.max_seq_len, eng.decode_chunk) == (
        s["num_slots"], s["max_seq_len"], s["decode_chunk"])
    state = eng._abstract_state()
    assert state.cache.k.shape[0] == config["num_hidden_layers"]


def test_a_launcher_cannot_pass_an_engine_lever(tmp_path, monkeypatch):
    import jax

    from benchmark import rehearse_compile
    from kukeon_tpu.parallel import make_mesh

    config = run.load_cell(inproc.FIXTURES, "tiny.independent")["config"]
    dense = plugins.load("launchers", "dense_gqa")
    monkeypatch.setattr(dense, "abstract", lambda c, was=dense.abstract: {
        **was(c), "decode_chunk": 4})
    with pytest.raises(TypeError, match="decode_chunk"):
        rehearse_compile.abstract_engine(
            config, make_mesh(tensor=1, devices=jax.devices()[:1]))


class _DeadChild:
    """A cell that ends where a test says."""

    def __init__(self, at):
        self.at = at

    def start(self):
        if self.at == "boot":
            raise SystemExit("benchmark: the cell process ended (exit code 1) "
                             "before its 'ready' record (RuntimeError: no "
                             "room\non the device). No result.")
        # a port nobody listens on: the first scrape raises, uncaught
        return {"port": 1, "device": {}, "boot_phases_s": {}, "levers": {}}

    def close(self):
        pass


@pytest.mark.parametrize("stage,raised,what", [
    ("boot", SystemExit, "exit code 1) before its 'ready' record "
     "(RuntimeError: no room on the device)"),
    ("warm-up", OSError, "ConnectionRefusedError: "),
])
def test_a_run_that_ends_without_a_result_says_where(
        stage, raised, what, tmp_path, capsys):
    """One line on standard output, ``benchmark: no result: <stage>: <what>``,
    for a SystemExit and for an uncaught exception alike; the run still ends
    as it would have."""
    spec = run.load_cell(inproc.FIXTURES, "tiny.independent")
    with pytest.raises(raised):
        run.drive(_DeadChild(stage), spec, 1, 1.0, False, str(tmp_path),
                  time.monotonic())
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith(f"benchmark: no result: {stage}: ") and what in last


def test_a_failed_reduction_says_so(tmp_path, capfd):
    """trace_reduce.py run for real over a capture that holds no trace: its
    own message stays on standard error, and the run ends with the exit code
    it always had (1, a SystemExit with a text)."""
    capture = {"status": 200, "rec": {"path": str(tmp_path / "nothing")}}
    with pytest.raises(SystemExit) as e:
        run.reduce_trace(capture, str(tmp_path))
    assert "trace_reduce.py exited with code 1" in str(e.value.code)
    assert "trace_reduce: no .xplane.pb under" in capfd.readouterr().err
    assert run.no_result_line("reduction", e.value).startswith(
        "benchmark: no result: reduction: benchmark: trace_reduce.py exited")


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
    assert _sound(out)
    assert set(out["metrics"]) == {"queue_wait_p90_ms", "ttft_p90_ms"}
    assert not set(out["metrics"]) & E2E                    # no e2e name
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) == 10
    assert 1 <= len(out["breakdown"]["idle_gaps"]) <= 10
    assert list(out)[-1] == "compared"
    json.dumps(out)


def test_the_dispatcher_lists_the_callbacks_it_never_came_to():
    """The close scrape is due 50 ms before the window's end; a host that
    stalls over that moment keeps the dispatcher from it. ``missed`` lists
    what was due inside the window and did not run, in due order; nothing of
    it runs here, and one due at or after the end is not listed."""
    from benchmark import loadgen

    class Nothing:
        def arrivals(self):
            return []

    ran = []
    at = [(0.95, lambda: ran.append("close")), (0.0, lambda: ran.append("open")),
          (1.0, lambda: ran.append("never"))]
    loop = loadgen.OpenLoop(
        0, Nothing(), time.monotonic() - 2.0, 1.0, 0.0, at)  # closed a second ago
    assert loop.run() == [] and ran == []
    assert [fn for _offset, fn in loop.missed] == [at[1][1], at[0][1]]
    assert [round(o, 6) for o, _fn in loop.missed] == [0.0, 0.95]


def _stalled(real):
    """An OpenLoop whose dispatcher never comes to a callback due after the
    window opened, as under a host that stalls over those moments."""
    def make(port, gen, t0, seconds, drain_s, at):
        loop = real(port, gen, t0, seconds, drain_s,
                    [a for a in at if a[0] <= 0.0])
        run_ = loop.run

        def run():
            records = run_()
            loop.missed += sorted((a for a in at if a[0] > 0.0),
                                  key=lambda a: a[0])
            return records

        loop.run = run
        return loop
    return make


@pytest.mark.parametrize("trace", [False, True])
def test_a_stalled_dispatcher(trace, tmp_path, monkeypatch, capsys):
    """A scrape the dispatcher never came to runs when the window has closed,
    and the run says how late (standard output and the result); a capture is
    not started after the window: the traced run ends without a result, at
    stage capture."""
    from benchmark import loadgen

    monkeypatch.setattr(loadgen, "OpenLoop", _stalled(loadgen.OpenLoop))
    if trace:
        with pytest.raises(SystemExit, match="not started inside the window"):
            _drive("tiny.sessions", 33, tmp_path, seconds=3.0, trace=True)
        last = capsys.readouterr().out.strip().splitlines()[-1]
        assert last.startswith("benchmark: no result: capture: ")
        return
    out, _spec = _drive("tiny.independent", 33, tmp_path, seconds=3.0)
    late = out["window_scrapes_late_ms"]
    assert late["close"] >= 50.0 and abs(late["open"]) < late["close"]
    assert _sound(out)
    assert f"close ran {late['close']:.1f} ms late" in capsys.readouterr().out
