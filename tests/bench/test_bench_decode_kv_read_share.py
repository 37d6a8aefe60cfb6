"""``decode_kv_read_share`` (benchmark/layer_metrics): the window's growth of
``kukeon_engine_decode_kv_rows_total{what="read"}`` over ``{what="held"}``, in
percent. Over a recorded scrape pair it gives the hand-computed share; on a
program without the counter (the parent of the PR that brought it), or a window
in which no chunk was dispatched, it gives None and never raises; and
BENCHMARK.json lists it for every cell under the layer "ops"."""

import json
import os

import pytest

from benchmark import plugins, run, stats

NAME = "decode_kv_read_share"
ROWS = "kukeon_engine_decode_kv_rows_total"

# Two scrapes of one engine as /metrics prints them (8 slots x 2048 rows x 32
# layers; chunks of 4 steps): the window between them dispatched 100 chunks.
OPEN = """\
# HELP kukeon_engine_decode_kv_rows_total Cache rows by dispatched decode chunk
# TYPE kukeon_engine_decode_kv_rows_total counter
kukeon_engine_decode_kv_rows_total{what="held"} 209715200
kukeon_engine_decode_kv_rows_total{what="read"} 31457280
kukeon_engine_decode_chunks_total{k="4"} 100
"""
CLOSE = """\
kukeon_engine_decode_kv_rows_total{what="held"} 419430400
kukeon_engine_decode_kv_rows_total{what="read"} 57671680
kukeon_engine_decode_chunks_total{k="4"} 200
"""


def _read(ctx):
    return plugins.load("layer_metrics", NAME).read(ctx)


def _ctx(before: str, after: str) -> dict:
    return {"metrics_open": stats.parse_prometheus(before),
            "metrics_close": stats.parse_prometheus(after)}


def test_the_share_of_a_recorded_scrape_pair():
    # read grew by 26214400 of held's 209715200: an eighth
    assert _read(_ctx(OPEN, CLOSE)) == pytest.approx(12.5)


@pytest.mark.parametrize("held, read, want", [
    (1000, 1000, 100.0),        # the XLA body: every held row is read
    (1000, 0, 0.0),             # chunks over slots that hold nothing yet
    (786432, 243712, 30.99),    # two kinds: rings and a full stack
])
def test_the_share_is_read_over_held(held, read, want):
    after = (f'{ROWS}{{what="held"}} {held}\n{ROWS}{{what="read"}} {read}\n')
    assert _read(_ctx("", after)) == pytest.approx(want, abs=0.01)


@pytest.mark.parametrize("before, after", [
    pytest.param("", "", id="no scrape holds the family"),
    pytest.param('kukeon_engine_decode_chunks_total{k="4"} 100\n',
                 'kukeon_engine_decode_chunks_total{k="4"} 200\n',
                 id="the parent: chunks counted, rows not"),
    pytest.param(OPEN, OPEN, id="no chunk dispatched in the window"),
    pytest.param(CLOSE, OPEN, id="a counter that went backwards"),
    pytest.param("", f'{ROWS}{{what="read"}} 5\n', id="read without held"),
])
def test_none_and_no_exception_where_there_is_nothing_to_read(before, after):
    assert _read(_ctx(before, after)) is None


def test_benchmark_json_lists_it_for_every_cell_as_the_ops_layers():
    with open(os.path.join(plugins.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "ops",
                     "moves": "latency_mean_ms"}
    assert bench["per_layer"][-1] == entry      # appended, nothing moved
    held = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert "workloads" not in held
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}
    for w in bench["workloads"]:
        spec = run.load_cell(plugins.REPO, w["name"])
        assert NAME in [m["name"] for m in spec["per_layer"]], w["name"]
    assert os.path.exists(os.path.join(plugins.HERE, "layer_metrics",
                                       NAME + ".py"))
