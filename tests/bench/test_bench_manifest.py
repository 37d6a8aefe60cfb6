"""BENCHMARK.json against the contract's own rules."""

import json
import os
import re

import pytest

from benchmark import plugins

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(plugins.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == KEYS
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert os.path.getsize(os.path.join(plugins.REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    cells = len(bench["workloads"])
    # a full check has to fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(bench["configs"]) <= 24


def test_names_and_units(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_moves_is_reported_by_each_of_the_metrics_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_every_cells_files_exist_and_every_config_has_a_cell(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(plugins.REPO, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["serving"]["chips"] == w["chips"]
        assert cfg["name"] == w["config"] and cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        with open(os.path.join(plugins.HERE, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for kind, name in (("generators", mix["generator"]),
                           ("reference", cfg["reference"])):
            assert os.path.exists(os.path.join(plugins.HERE, kind, name + ".py"))
        assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    assert used == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(plugins.HERE, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips(bench):
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_reduced_never_names_a_width(bench):
    width = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                       r"head_dim|expansion|experts_per_tok)")
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not width.search(key), key


def test_command_names_no_file_outside_paths(bench):
    assert len(bench["command"]) <= 32
    for word in bench["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in bench["paths"])


def test_files_under_paths_are_named_from_a_names_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(plugins.REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), plugins.REPO)
                assert ok.match(rel), rel


def _client_stats():
    from benchmark import stats

    rec = {"ok": True, "ttft_ms": 100.0, "tpot_ms": 20.0, "latency_ms": 500.0}
    return stats.end_to_end([rec], 1.0, {"ttft_ms": 1000.0, "tpot_ms": 60.0},
                            10, 9000.0)


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer(bench):
    """What a cell reports end to end is a key of the client's arithmetic
    (run.py looks it up there), beside setup_s."""
    from benchmark import run

    have = set(_client_stats()) | {"setup_s"}
    for w in bench["workloads"]:
        spec = run.load_cell(plugins.REPO, w["name"])
        names = [m["name"] for m in spec["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert set(names) <= have, (w["name"], set(names) - have)
        assert spec["per_layer"], w["name"]


@pytest.mark.parametrize("name", ["ttft_mean_ms", "tpot_mean_ms",
                                  "out_tok_per_s"])
def test_a_client_reader_hands_on_the_traced_windows_own_number(name):
    """A metric held end to end in one cell and too unsteady for a bound in
    another stands there per layer, under a name of its own."""
    client = _client_stats()
    reader = plugins.load("layer_metrics", "client_" + name)
    assert reader.read({"client": client}) == client[name]
