"""``held_experts_reached_share`` (benchmark/layer_metrics): the window's growth
of ``kukeon_moe_held_experts_reached_total`` over ``kukeon_moe_held_experts_total``,
in percent. Over a recorded scrape pair it gives the hand-computed share; on a
program without the counters (the parent of the PR that brought them), or a
window in which no expert layer ran, it gives None and never raises; and
BENCHMARK.json lists it for the three cells that hold an expert layer."""

import json
import os

import pytest

from benchmark import plugins, run, stats

NAME = "held_experts_reached_share"
HELD = "kukeon_moe_held_experts_total"
REACHED = "kukeon_moe_held_experts_reached_total"
CELLS = ["trinity-ep8.mixed-lengths", "deepseek-v32-ep16.long-context",
         "granite4h-ep2.retrieval"]

# Two scrapes of one engine as /metrics prints them (36 held experts x 40
# layers a call): the window between them ran 1000 decode steps that reached
# 20 of a layer's 36 and two prefills of two pieces that reached them all.
OPEN = """\
# HELP kukeon_moe_held_experts_total Summed on the device by the model's forwards
# TYPE kukeon_moe_held_experts_total counter
kukeon_moe_held_experts_total 144000
kukeon_moe_held_experts_reached_total 90000
kukeon_moe_held_hits_total 7000
"""
CLOSE = """\
kukeon_moe_held_experts_total 1589760
kukeon_moe_held_experts_reached_total 895760
kukeon_moe_held_hits_total 99000
"""


def _read(ctx):
    return plugins.load("layer_metrics", NAME).read(ctx)


def _ctx(before: str, after: str) -> dict:
    return {"metrics_open": stats.parse_prometheus(before),
            "metrics_close": stats.parse_prometheus(after)}


def test_the_share_of_a_recorded_scrape_pair():
    # held grew by 1004 calls x 1440, reached by 1000 x 800 + 4 x 1440
    assert _read(_ctx(OPEN, CLOSE)) == pytest.approx(
        100 * 805760 / 1445760)


@pytest.mark.parametrize("held, reached, want", [
    (1440, 1440, 100.0),        # every slot's rows routed: every expert read
    (1440, 0, 0.0),             # steps whose tokens all chose experts elsewhere
    (1152000, 704000, 61.11),   # 22 of 36 a layer
])
def test_the_share_is_reached_over_held(held, reached, want):
    after = f"{HELD} {held}\n{REACHED} {reached}\n"
    assert _read(_ctx("", after)) == pytest.approx(want, abs=0.01)


@pytest.mark.parametrize("before, after", [
    pytest.param("", "", id="no scrape holds the family"),
    pytest.param("kukeon_moe_held_hits_total 7000\n",
                 "kukeon_moe_held_hits_total 99000\n",
                 id="the parent: hits counted, held experts not"),
    pytest.param(OPEN, OPEN, id="no expert layer ran in the window"),
    pytest.param(CLOSE, OPEN, id="a counter that went backwards"),
    pytest.param("", f"{REACHED} 5\n", id="reached without held"),
])
def test_none_and_no_exception_where_there_is_nothing_to_read(before, after):
    assert _read(_ctx(before, after)) is None


def test_benchmark_json_lists_it_for_the_cells_that_hold_an_expert_layer():
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

    assert (HELD, REACHED) == expert_layer.TALLY[1:]
    for family in ("window_moe", "sparse_latent_moe", "ssm_moe"):
        module = importlib.import_module(f"kukeon_tpu.models.{family}")
        assert {HELD, REACHED} <= set(module.COUNTERS), family
