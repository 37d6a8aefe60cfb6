"""``routed_rows_worked_share`` (benchmark/layer_metrics): the window's growth
of ``kukeon_moe_pair_rows_worked_total`` over ``kukeon_moe_pair_rows_total``, in
percent. Over a recorded scrape pair it gives the hand-computed share; on a
program without the counters (the parent of the PR that brought them), or a
window in which no expert layer ran, it gives None and never raises; and
BENCHMARK.json lists it for the four cells that hold an expert layer."""

import json
import os

import pytest

from benchmark import plugins, run, stats

NAME = "routed_rows_worked_share"
PAIRS = "kukeon_moe_pair_rows_total"
WORKED = "kukeon_moe_pair_rows_worked_total"
CELLS = ["trinity-ep8.mixed-lengths", "deepseek-v32-ep16.long-context",
         "granite4h-ep2.retrieval", "dots3-ep8.notes-and-drafts"]

# Two scrapes of one engine as /metrics prints them (ten expert layers, top-10
# of 32 slots a decode step, of 2048 rows a prefill piece): the window between
# them ran 1000 decode steps that held a pair in 9 layers of 10 and 6 prefill
# pieces whose held pairs filled 5, 6, 5, 6, 5 and 6 blocks of 2048 a layer.
OPEN = """\
# HELP kukeon_moe_pair_rows_total Summed on the device by the model's forwards
# TYPE kukeon_moe_pair_rows_total counter
kukeon_moe_pair_rows_total 3200000
kukeon_moe_pair_rows_worked_total 2880000
kukeon_moe_held_hits_total 7000
"""
CLOSE = """\
kukeon_moe_pair_rows_total 7628800
kukeon_moe_pair_rows_worked_total 6435840
kukeon_moe_held_hits_total 99000
"""


def _read(ctx):
    return plugins.load("layer_metrics", NAME).read(ctx)


def _ctx(before: str, after: str) -> dict:
    return {"metrics_open": stats.parse_prometheus(before),
            "metrics_close": stats.parse_prometheus(after)}


def test_the_share_of_a_recorded_scrape_pair():
    pairs = 1000 * 10 * 320 + 6 * 10 * 20480
    worked = 1000 * 9 * 320 + 33 * 10 * 2048
    assert (pairs, worked) == (7628800 - 3200000, 6435840 - 2880000)
    assert _read(_ctx(OPEN, CLOSE)) == pytest.approx(100 * worked / pairs)


@pytest.mark.parametrize("pairs, worked, want", [
    (20480, 12288, 60.0),       # 10449 held pairs of a piece in six blocks
    (16384, 0, 0.0),            # a piece whose tokens all chose experts elsewhere
    (256, 256, 100.0),          # a decode step is one block, walked or not
    (5120, 6144, 120.0),        # blocks that do not divide the pairs, all held
])
def test_the_share_is_worked_over_pairs(pairs, worked, want):
    after = f"{PAIRS} {pairs}\n{WORKED} {worked}\n"
    assert _read(_ctx("", after)) == pytest.approx(want, abs=0.01)


@pytest.mark.parametrize("before, after", [
    pytest.param("", "", id="no scrape holds the family"),
    pytest.param("kukeon_moe_held_hits_total 7000\n",
                 "kukeon_moe_held_hits_total 99000\n",
                 id="the parent: hits counted, pair rows not"),
    pytest.param(OPEN, OPEN, id="no expert layer ran in the window"),
    pytest.param(CLOSE, OPEN, id="a counter that went backwards"),
    pytest.param("", f"{WORKED} 2048\n", id="worked without pairs"),
])
def test_none_and_no_exception_where_there_is_nothing_to_read(before, after):
    assert _read(_ctx(before, after)) is None


def test_benchmark_json_lists_it_for_the_four_cells_that_hold_an_expert_layer():
    with open(os.path.join(plugins.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "model step",
                     "moves": "latency_mean_ms", "workloads": CELLS}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}
    for w in bench["workloads"]:
        names = [m["name"] for m in run.load_cell(
            plugins.REPO, w["name"])["per_layer"]]
        assert (NAME in names) == (w["name"] in CELLS), w["name"]
    assert os.path.exists(os.path.join(plugins.HERE, "layer_metrics",
                                       NAME + ".py"))


def test_the_programs_counters_are_the_ones_it_reads():
    import importlib

    from kukeon_tpu.models import expert_layer

    assert (PAIRS, WORKED) == expert_layer.PAIR_ROWS == expert_layer.COUNTS[3:]
    for family in ("window_moe", "sparse_latent_moe", "ssm_moe"):
        module = importlib.import_module(f"kukeon_tpu.models.{family}")
        assert {PAIRS, WORKED} <= set(module.COUNTERS), family
