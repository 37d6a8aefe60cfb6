"""The trace reduction: on hand-made lines, and on the small trace recorded on
the chip (tests/bench/fixtures/trace-small.json, written by
trace_reduce.read_planes from a real capture and trimmed)."""

import json
import os

import pytest

import inproc
from benchmark import trace_reduce as tr

LINES = {
    "XLA Modules": [("jit_prefill(11)", 1.0, 0.30), ("jit_insert(12)", 1.31, 0.01),
                    ("jit_decode_chunk_fn(13)", 1.50, 0.40),
                    ("jit_decode_chunk_fn(13)", 2.10, 0.40)],
    "XLA Ops": [("fusion.1", 1.00, 0.10), ("all-reduce.3", 1.10, 0.05),
                ("fusion.2", 1.15, 0.15), ("copy.1", 1.31, 0.01),
                ("while.7", 1.50, 0.40), ("fusion.9", 1.50, 0.20),
                ("fusion.9", 1.70, 0.20), ("while.7", 2.10, 0.40),
                ("fusion.9", 2.10, 0.40)],
}


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.union([]) == []


def test_leaf_events_drop_what_spans_other_events():
    leaves = tr.leaf_events(LINES["XLA Ops"])
    assert "while.7" not in [n for n, _s, _d in leaves]
    assert sum(d for n, _s, d in leaves if n == "fusion.9") == pytest.approx(0.8)


def test_reduce_device_busy_modules_collectives_and_gaps():
    d = tr.reduce_device(LINES, 0.5, 3.0)
    assert d["window_s"] == pytest.approx(2.5)
    assert d["busy_s"] == pytest.approx(0.30 + 0.01 + 0.40 + 0.40)
    assert d["collective_s"] == pytest.approx(0.05)
    assert d["modules"]["jit_decode_chunk_fn"]["count"] == 2
    assert d["modules"]["jit_decode_chunk_fn"]["seconds"] == pytest.approx(0.8)
    assert d["modules"]["jit_prefill"]["events"][0] == [pytest.approx(0.5), 0.30, "11"]
    assert d["top_ops"][0] == ["fusion.9", pytest.approx(0.8)]
    gaps = {label: s for label, s in d["idle_gaps"]}
    assert gaps["start->jit_prefill"] == pytest.approx(0.5)
    assert gaps["jit_decode_chunk_fn->end"] == pytest.approx(0.5)
    assert gaps["jit_insert->jit_decode_chunk_fn"] == pytest.approx(0.18)
    assert gaps["jit_decode_chunk_fn->jit_decode_chunk_fn"] == pytest.approx(0.2)


def test_module_names_lose_their_program_id():
    assert tr.module_of("jit_prefill_ext(123456)") == ("jit_prefill_ext", "123456")
    assert tr.module_of("odd name") == ("odd name", "")


@pytest.mark.parametrize("name", ["all-reduce.1", "%all-gather.7", "reduce-scatter",
                                  "all-to-all.2", "collective-permute.3"])
def test_collective_filter_takes(name):
    assert tr.COLLECTIVE.match(name)


@pytest.mark.parametrize("name", ["fusion.3", "reduce.1", "copy.2", "all-reducer"])
def test_collective_filter_leaves(name):
    assert not tr.COLLECTIVE.match(name) or name == "all-reducer"


# --- the small trace recorded on the chip -----------------------------------

@pytest.fixture(scope="module")
def recorded():
    import gzip

    path = os.path.join(inproc.FIXTURES, "trace-small.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)["planes"]
    for p in planes:
        p["lines"] = {k: [tuple(e) for e in v] for k, v in p["lines"].items()}
    return planes


@pytest.fixture
def reduced(recorded, monkeypatch):
    monkeypatch.setattr(tr, "read_planes", lambda path: recorded)
    return tr.reduce("recorded")


def test_recorded_trace_busy_union_against_a_microsecond_grid(recorded, reduced):
    dev = reduced["devices"][0]
    ops = recorded[0]["lines"]["XLA Ops"]
    lo = min(s for _n, s, _d in ops)
    grid = bytearray(int(dev["window_s"] * 1e6) + 2)
    for _n, s, d in ops:
        a, b = int(round((s - lo) * 1e6)), int(round((s - lo + d) * 1e6))
        grid[a:b] = b"\x01" * (b - a)
    assert dev["busy_s"] == pytest.approx(sum(grid) * 1e-6, rel=0.02)
    assert 0 < dev["busy_s"] < dev["window_s"] < 0.16
    assert sum(g for _l, g in dev["idle_gaps"]) <= dev["window_s"] - dev["busy_s"] + 1e-9
    assert reduced["planes"] == ["/device:TPU:0", "/host:CPU"]


def test_recorded_trace_modules_and_decode_steps(recorded, reduced):
    mods = reduced["devices"][0]["modules"]
    assert mods["jit_prefill_ext"]["count"] == 1
    assert mods["jit_prefill_ext"]["seconds"] == pytest.approx(0.030846687)
    assert mods["jit_insert"]["count"] == 1
    assert mods["jit_decode_chunk_fn"]["count"] == 1
    # one prefill_ext: every instruction of its layer scan ran 32 times
    assert list(mods["jit_prefill_ext"]["max_op_count"].values()) == [32]
    # the clipped decode chunk: its layer scan is `while.53` in this capture
    (most,) = mods["jit_decode_chunk_fn"]["max_op_count"].values()
    d = [e for e in recorded[0]["lines"]["XLA Modules"]
         if e[0].startswith("jit_decode_chunk_fn")][0]
    whiles = [e for e in recorded[0]["lines"]["XLA Ops"]
              if e[0].startswith("%while.53 ") and e[1] >= d[1] - 1e-9]
    assert len(whiles) >= 5 and most // 32 in (len(whiles), len(whiles) - 1)
    assert 0.012 < mods["jit_decode_chunk_fn"]["seconds"] / (most / 32) < 0.017


def test_recorded_trace_top_ops_are_leaves_with_short_labels(reduced):
    dev = reduced["devices"][0]
    labels = [n for n, _s in dev["top_ops"]]
    assert not any(n.startswith("while") for n in labels)
    assert all(len(n) <= 120 for n in labels)
    assert any("<- s8[32,4096,14336]" in n for n in labels)     # an MLP product
    secs = [s for _n, s in dev["top_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= dev["busy_s"] * 1.001
    assert dev["collective_s"] == 0                      # one chip: none
    assert all("->" in label for label, _g in dev["idle_gaps"])


def test_layer_metric_readers_on_the_recorded_trace(reduced):
    from benchmark import plugins

    with open(os.path.join(plugins.HERE, "configs",
                           "mistral-7b-v0.3-int8.json")) as f:
        config = json.load(f)
    with open(os.path.join(plugins.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    before = {"kukeon_program_tokens_total": [({"program": "prefill"}, 1000.0)],
              "kukeon_program_dispatch_total": [({"program": "prefill"}, 3.0),
                                                ({"program": "prefill_ext"}, 5.0)]}
    after = {"kukeon_program_tokens_total": [({"program": "prefill"}, 1512.0)],
             "kukeon_program_dispatch_total": [({"program": "prefill"}, 3.0),
                                               ({"program": "prefill_ext"}, 7.0)]}
    ctx = {"trace": reduced, "config": config, "peaks": peaks,
           "pkg_dir": plugins.HERE, "device": {"kind": "TPU v5 lite"},
           "capture": {"metrics_before": before, "metrics_after": after,
                       "requested": 10.0, "duration_s": 3.0},
           "live": {"slots": 6.0, "kv_rows": 9000.0},
           "records": [{"token_times": [11.0, 11.5], "new_tokens": 200,
                        "prompt_len": 1500}]}

    def read(name):
        return plugins.load("layer_metrics", name).read(ctx)

    step = read("decode_step_dev_ms")
    assert 12.0 < step < 17.0
    # two dispatches of 256 padded tokens counted, one traced: 256 tokens
    assert read("prefill_dev_ms_per_ktok") == pytest.approx(30.846687 / 0.256)
    assert 40.0 < read("decode_chunk_roofline") < 100.0
    assert 0.0 < read("prefill_roofline") < 100.0
    assert 0.0 < read("device_idle_share") < 100.0
    assert read("collective_share") == 0.0
    ctx["device"] = {"kind": "TPU v9"}
    with pytest.raises(SystemExit, match="no peaks"):
        read("decode_chunk_roofline")
