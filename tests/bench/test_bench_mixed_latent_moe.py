"""The two-kind latent decoder's benchmark files (``mixed_latent_moe``: window
layers with a latent attention of their own among selecting latent layers):
the manifest's entries, the configuration against the catalog, the weights the
reference defines against the program's draw, the reference in blocks against
itself whole, the operation counts against numbers worked by hand, the readers
on made-up captures and on the cell's own ``/metrics`` text, and a whole run of
a tiny cell in this process."""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import inproc
import test_bench_harness as harness
from benchmark import plugins, run, stats
from benchmark.layer_metrics import _mixed_latent as mlr
from kukeon_tpu.models import sparse_latent_moe as slm

FAMILY = os.path.join(inproc.FIXTURES, "mixed-latent-moe")
CONFIG = "dots3-note-prev-ep8-bf16"
CELL = "dots3-ep8.notes-and-drafts"
METRICS = ("mixed_latent_decode_step_dev_ms", "mixed_latent_decode_roofline",
           "mixed_latent_prefill_roofline", "window_latent_attention_roofline",
           "window_latent_rows_read_share",
           "mixed_latent_held_experts_reached_share")
ref = plugins.load("reference", "mixed_latent_moe")
launcher = plugins.load("launchers", "mixed_latent_moe")


def _file(kind, name):
    with open(os.path.join(plugins.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FAMILY, "configs",
                           "tiny-mixed-latent-moe.json")) as f:
        return json.load(f)


# --- the manifest and the configuration file ----------------------------------

def test_the_manifest_gains_one_configuration_one_cell_and_six_metrics():
    bench = _file("..", "BENCHMARK")
    entry = bench["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"], entry["chips"]
            ) == (CELL, CONFIG, "notes-and-drafts", 1)
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["configs"][-1]["reduced"] == _file("configs", CONFIG)["reduced"]
    rate = _file("traffic", "notes-and-drafts")["params"]["rate_per_s"]
    assert f"Poisson {rate}/s" in entry["why"]
    assert len(entry["why"]) <= 200
    assert len(bench["configs"][-1]["why"]) <= 200
    mine = bench["per_layer"][-len(METRICS):]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "latency_mean_ms"
        assert ("roofline" in m["name"]) == (m["unit"] == "%"
                                             and m["source"] == "device_trace"
                                             and m["layer"] == "ops")
    # no metric that was there lists this cell
    assert not [m["name"] for m in bench["per_layer"][:-len(METRICS)]
                if CELL in m.get("workloads", ())]
    spec = run.load_cell(plugins.REPO, CELL)
    reported = {m["name"] for m in spec["per_layer"]}
    assert set(METRICS) <= reported
    # the metrics without a list
    assert {"ttft_p90_ms", "tpot_p90_ms", "queue_wait_p90_ms",
            "device_idle_share", "decode_chunk_steps_mean",
            "decode_kv_read_share", "request_queued_ms", "request_prefill_ms",
            "request_decode_ms_per_token"} <= reported
    assert {m["name"] for m in spec["end_to_end"]} == {
        "latency_mean_ms", "slo_share", "setup_s"}


def test_the_file_holds_every_published_width_and_states_its_cut():
    cfg = _file("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
            if key != "layer_types":    # said in words
                assert cfg["published"][key] == published
        else:
            assert cfg[key] == published, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "n_routed_experts", "vocab_size",
                              "max_position_embeddings"]
    assert set(cfg["reduced"]) == set(cfg["published"])
    # published layer 0 and one whole period
    assert cfg["layer_types"] == row["config"]["layer_types"][:5] == [
        "full_attention", "full_attention"] + ["sliding_attention"] * 3
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 32]
    assert cfg["router_experts"] == row["config"]["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    s = cfg["serving"]
    assert (s["num_slots"], s["max_seq_len"], s["chips"], s["decode_chunk"],
            s["max_pending"], s["kv_cache_int8"], s["kv_page_tokens"]) == (
        32, 20480, 1, 16, 64, False, 0)
    assert s["max_seq_len"] == cfg["max_position_embeddings"]
    assert len(cfg["assumed"]) >= 8 and "eight-chip" in cfg["deployment"]
    for reading in ("apply_mla_qkv_lora_rescale", "headwise",
                    "sliding_window_size 513", "towers"):
        assert any(reading in a for a in cfg["assumed"]), reading
    program = launcher.program_config(cfg)
    assert (program.full.softmax_scale, program.sliding.softmax_scale) == (
        192 ** -0.5, 256 ** -0.5)
    latent, ring = program.cache_kinds(20480)
    assert (latent.name, latent.rows, latent.select) == ("latent", 20480, 2048)
    assert dict(latent.arrays) == {"kidx": 128, "ckv": 640}
    assert (ring.name, ring.rows, ring.window) == ("window_latent", 512, 513)
    assert dict(ring.arrays) == {"wckv": 1152}
    limits = cfg["check"]["limits"]
    assert set(limits) == {"gap_max", "gap_mean"} and "a8" in cfg["check"][
        "set_from"]


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "yarn"}), ("attention_gate_type", "elementwise"),
    ("swa_attention_gate_type", None), ("n_routed_experts", 256),
    ("tie_word_embeddings", True), ("swa_num_key_value_heads", 8),
    ("layer_types", ["full_attention"] * 4)])
def test_the_launcher_refuses_keys_the_program_cannot_state(key, value):
    cfg = {**_file("configs", CONFIG), key: value}
    with pytest.raises(SystemExit, match="cannot state"):
        launcher.program_config(cfg)


def test_the_mix_keeps_every_prompt_past_the_window_and_the_long_past_the_selection():
    mix, cfg = _file("traffic", "notes-and-drafts"), _file("configs", CONFIG)
    assert mix["params"]["shape_seed"] == 20261046
    assert mix["generator"] == "independent"
    assert mix["limits"] == {"ttft_ms": 4000.0, "tpot_ms": 80.0}
    assert mix["warmup"] == {"prefill": [1024, 2048, 4096, 8192, 16384],
                             "decode_chunk": [1, 4, 16]}
    gen = plugins.load("generators", mix["generator"]).Generator(
        mix["params"], 1, cfg["vocab_size"], 51.0)
    reqs = gen.arrivals()
    lens = [r["new_tokens"] for r in reqs]
    assert all(r["prefix_id"] is None for r in reqs)
    assert min(lens) > cfg["sliding_window_size"]
    long_ = [n for n in lens if n >= 6144]
    assert long_ and min(long_) > cfg["index_topk"]
    assert 0.25 < len(long_) / len(lens) < 0.65
    assert max(r["new_tokens"] + r["max_new_tokens"] for r in reqs) \
        <= cfg["serving"]["max_seq_len"]
    answers = [r["max_new_tokens"] for r in reqs]
    assert min(answers) >= 32 and max(answers) <= 1536
    assert sum(a >= 512 for a in answers) >= len(answers) // 4


# --- the weights --------------------------------------------------------------

@pytest.mark.parametrize("seed,dtype", [(0, "float32"),
                                        (2147483000, "bfloat16")])
def test_the_program_draws_the_weights_the_benchmark_defines(seed, dtype):
    cfg = {**_tiny(), "torch_dtype": dtype}
    program = launcher.program_config(cfg)
    params = slm.init_params(jax.random.key(seed), program)
    root = jax.random.key(seed)
    dt = getattr(jnp, dtype)
    first, count = cfg["experts_held"]
    H, Im = 64, 48

    def same(got, want, held_in=dtype):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        if held_in == "float32":
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
        else:
            np.testing.assert_allclose(got, want, rtol=2 ** -7)
            assert (got != want).mean() <= 1e-4

    def mat(name, shape, fan_in, layer=None, expert=None, dtype=dt):
        return ref._matrix(ref._key(root, name, layer, expert), shape, fan_in,
                           "f32", dtype)

    same(params["embed"], mat("embed", (384, H), 1))      # unit variance
    same(params["lm_head"], mat("lm_head", (H, 384), H))
    dense, full, window = (params["layers"][i] for i in (0, 1, 3))
    # what reads a rescaled latent is drawn at the model's width, 64
    same(dense["wq_b"], mat("wq_b", (48, 4 * 24), H, 0))
    same(full["wi_q"], mat("wi_q", (48, 16 * 16), H, 1))
    same(dense["wg"], mat("wg", (H, 4), H, 0))
    # a window layer draws its OWN shapes: 2 heads of 24 + 8, ranks 40 and 56
    same(window["wq_a"], mat("wq_a", (H, 40), H, 3))
    same(window["wq_b"], mat("wq_b", (40, 2 * 32), H, 3))
    same(window["wkv_a"], mat("wkv_a", (H, 56 + 8), H, 3))
    same(window["wg"], mat("wg", (H, 2), H, 3))
    both = np.asarray(mat("wkv_b", (56, 2 * 40), H, 3)).reshape(56, 2, 40)
    same(window["wkv_bk"], both[..., :24].transpose(1, 2, 0))
    same(window["wkv_bv"], both[..., 24:].transpose(1, 0, 2))
    assert not {"wi_q", "wi_k", "wi_w"} & set(window)
    same(full["wi_k_shift"], ref._gain(
        ref._key(root, "wi_k_shift", 1), (16,), dt, ref.SHIFT_STD, 0.0))
    same(window["router"], mat("router", (H, 16), H, 3, dtype=jnp.float32),
         held_in="float32")
    # fitted to the layer's own router and norm, by each side's own code
    fitted = ref.selection_bias(
        ref._key(root, "bias", 3),
        mat("router", (H, 16), H, 3, dtype=jnp.float32),
        ref._gain(ref._key(root, "norm2", 3), (H,), dt), ref.dims(cfg))
    np.testing.assert_allclose(window["bias"], fitted, atol=2e-5)
    for i in range(count):      # the experts this chip holds, by their number
        same(window["e_down"][i], mat("e_down", (Im, H), Im, 3, first + i))
    # the gate's key follows the leaves the family had: theirs do not move
    assert ref.LEAVES == slm.LEAVES + slm.GATE_LEAVES


@pytest.mark.parametrize("precision,least", [("a8", 0.005), ("w4", 0.3)])
def test_lower_precision_moves_the_logits(precision, least, capsys):
    cfg = _tiny()
    toks = np.random.default_rng(3).integers(0, 384, 60).astype(np.int32)
    at = [np.arange(20, 59)]
    full = ref.logits_at(cfg, 3, [toks], at, 64)[0]
    low = ref.logits_at(cfg, 3, [toks], at, 64, precision=precision)[0]
    gap = full.max(-1) - full[np.arange(39), low.argmax(-1)]
    assert gap.max() > least
    assert "have a router near-tie" in capsys.readouterr().out


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Query blocks of 16 (four a sequence, a window layer's slice of keys
    starting before position 0 in the first) against one block of 64."""
    cfg = _tiny()
    toks = np.random.default_rng(4).integers(0, 384, 60).astype(np.int32)
    at = [np.arange(0, 59)]
    whole = ref.logits_at(cfg, 9, [toks], at, 64)[0]
    for name in ("Q_BLOCK", "I_BLOCK", "HEAD_GROUP"):
        monkeypatch.setattr(ref, name, {"HEAD_GROUP": 2}.get(name, 16))
    for fn in (ref._layer, ref._embed, ref._head, ref._fitted_bias):
        fn.clear_cache()
    blocks = ref.logits_at(cfg, 9, [toks], at, 64)[0]
    for fn in (ref._layer, ref._embed, ref._head, ref._fitted_bias):
        fn.clear_cache()
    np.testing.assert_allclose(blocks, whole, atol=2e-5)


def test_the_reference_pads_a_sequence_to_one_of_a_few_sizes():
    assert [ref.padded(n, 20480) for n in (700, 1024, 1025, 9000, 16384,
                                           16385, 20000)] \
        == [1024, 1024, 2048, 16384, 16384, 20480, 20480]
    assert ref.padded(50, 128) == 128


# --- opcount -----------------------------------------------------------------

FULL = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 128 * 128 * 5120 + 5120 * 128)
INDEXER = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
WINDOW = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
          + 64 * 128 * 5120 + 5120 * 64)
EXPERT = 3 * 5120 * 1536
DENSE_MLP = 3 * 5120 * 13824
HEAD = 5120 * 19008
ROUTER = 5120 * 256


def test_the_weights_are_8_18_gb():
    total = 2 * (2 * (FULL + INDEXER) + 3 * WINDOW + DENSE_MLP
                 + 4 * 33 * EXPERT + 2 * HEAD) + 4 * 4 * ROUTER
    assert (FULL + INDEXER) / 1e6 == pytest.approx(144.0, abs=0.1)
    assert WINDOW / 1e6 == pytest.approx(90.8, abs=0.1)
    assert EXPERT / 1e6 == pytest.approx(23.6, abs=0.05)
    assert total / 1e9 == pytest.approx(8.18, abs=0.01)


def test_decode_step_bytes_and_flops_count_what_a_step_needs():
    count = plugins.load("opcount", "mixed_latent_decode_chunk").count
    cfg = _file("configs", CONFIG)
    got = count(cfg, 4, 40000)           # four slots of 10000 tokens
    read = 32 * (1 - (1 - 8 / 256) ** 4)            # held experts a layer reaches
    weights = 2 * (2 * (FULL + INDEXER) + 3 * WINDOW + DENSE_MLP + 4 * EXPERT
                   + HEAD + 4 * read * EXPERT) + 4 * 4 * ROUTER
    cache = 2 * (2 * (40000 * 128 + 4 * 2048 * 576 + 4 * (128 + 576))
                 + 3 * (4 * 512 + 4) * 1088)
    assert got["bytes"] == pytest.approx(weights + cache + 4 * 5120 * 2)
    # the experts the tally counted, where the reader has them
    told = count(cfg, 4, 40000, experts_reached=5.0)
    assert got["bytes"] - told["bytes"] == pytest.approx(
        2 * 4 * (read - 5.0) * EXPERT)
    # a slot shorter than the window and the selection reads all its rows and
    # no more: 300 tokens a slot
    short = count(cfg, 4, 1200)
    assert (got["bytes"] - short["bytes"]) == pytest.approx(
        2 * (2 * (38800 * 128 + (4 * 2048 - 1200) * 576)
             + 3 * (4 * 512 - 1200) * 1088))
    hit = 4 * 8 * 32 / 256
    assert got["flops"] == pytest.approx(
        2 * (2 * (FULL + INDEXER) + 3 * WINDOW + DENSE_MLP + 4 * EXPERT
             + 4 * ROUTER + HEAD) * 4 + 2 * 4 * EXPERT * hit
        + 2 * (2 * 64 * 128 * 40000 + 2 * 128 * 4 * 2048 * (512 + 64 + 512))
        + 3 * 2 * 64 * 4 * 512 * (1024 + 64 + 1024))
    # at a small shape: the tiny fixture's numbers by hand
    tiny = count(_tiny(), 2, 20)
    attn = lambda h, q, r, nh, dn, dr, dv: (  # noqa: E731
        h * q + q * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
        + nh * dv * h + h * nh)
    f, w = attn(64, 48, 32, 4, 16, 8, 16), attn(64, 40, 56, 2, 24, 8, 16)
    idx = 48 * 16 * 16 + 64 * 16 + 64 * 16
    reach = 4 * (1 - (1 - 4 / 16) ** 2)
    assert tiny["bytes"] == pytest.approx(
        2 * (2 * (f + idx) + 2 * w + 3 * 64 * 128 + 3 * 3 * 64 * 48
             + 64 * 384 + 3 * reach * 3 * 64 * 48) + 4 * 3 * 64 * 16
        + 2 * (2 * (20 * 16 + 2 * 8 * 40 + 2 * (16 + 40))
               + 2 * (2 * 6 + 2) * 64) + 2 * 64 * 2)


def test_prefill_flops_count_the_selected_and_the_windows_pairs_only():
    mod = plugins.load("opcount", "mixed_latent_prefill")
    cfg = _file("configs", CONFIG)
    assert mod.selected_pairs(0, 300, 513) == 300 * 301 / 2
    assert mod.selected_pairs(0, 2000, 513) == sum(min(513, t + 1)
                                                   for t in range(2000))
    a, b = mod.count(cfg, 8192), mod.count(cfg, 16384)
    per_token = 2 * (2 * (FULL + INDEXER) + 3 * WINDOW + DENSE_MLP
                     + 4 * EXPERT * 2 + 4 * ROUTER)
    index = 2 * 2 * 64 * 128 * (16384 * 16385 - 8192 * 8193) / 2
    attend = 2 * 2 * 128 * 320 * 8192 * 2048     # rows past 2048 keep 2048
    band = 3 * 2 * 64 * 384 * 8192 * 513         # rows past 513 keep 513
    assert b["flops"] - a["flops"] == pytest.approx(
        per_token * 8192 + index + attend + band, rel=1e-9)
    assert b["bytes"] - a["bytes"] == 2 * 8192 * (2 * 704 + 3 * 1088)


def test_the_window_kernels_count_is_the_rows_inside_the_windows():
    count = plugins.load("opcount", "window_latent_decode_attention").count
    got = count(20, 20 * 512, 64, 1088, 1024)
    assert got["bytes"] == 2 * (20 * 512 * 1088 + 20 * 64 * (1088 + 1024)
                                + 20 * 1088)
    assert got["flops"] == 2 * 64 * (20 * 512 + 20) * (1088 + 1024)
    # bytes bound it on the v5e
    assert got["bytes"] / 819e9 > got["flops"] / 197e12


# --- readers -----------------------------------------------------------------

def _ctx(**over):
    cfg = _file("configs", CONFIG)
    ctx = {"config": cfg, "pkg_dir": plugins.HERE,
           "device": {"kind": "TPU v5 lite"},
           "peaks": _file(".", "peaks"),
           "metrics_open": {}, "metrics_close": {},
           "capture": {"metrics_before": {}, "metrics_after": {}},
           "records": [], "live": {"slots": 0.0, "kv_rows": 0.0},
           "trace": {"devices": [{"modules": {}}]}}
    ctx.update(over)
    return ctx


def _reader(name):
    return plugins.load("layer_metrics", name).read


def test_the_readers_find_nothing_on_another_programs_run():
    """A program without the counters, the kernel or the spans (the parent
    commit's, another family's): None from every one, and no exception."""
    ctx = _ctx()
    ctx["_mixed_latent_calls"] = None
    ctx["_spans"] = None
    for name in METRICS:
        assert _reader(name)(ctx) is None, name
    ctx["_mixed_latent_calls"] = [[0.001, 32, 64, 1024]]    # but no counter
    assert _reader("window_latent_attention_roofline")(ctx) is None
    del ctx["_mixed_latent_calls"]      # and no capture to pass over
    assert mlr.kernel_calls(ctx) is None


def test_the_counters_readers_divide_the_windows_deltas():
    held, reached = ("kukeon_moe_held_experts_total",
                     "kukeon_moe_held_experts_reached_total")
    open_ = stats.parse_prometheus(
        f"{mlr.READ} 1000\n{mlr.HELD} 5000\n{held} 128\n{reached} 100\n")
    close = stats.parse_prometheus(
        f"{mlr.READ} 52300\n{mlr.HELD} 1005000\n{held} 12928\n"
        f"{reached} 6500\n")
    ctx = _ctx(metrics_open=open_, metrics_close=close)
    assert _reader("window_latent_rows_read_share")(ctx) == pytest.approx(5.13)
    assert _reader("mixed_latent_held_experts_reached_share")(ctx) \
        == pytest.approx(50.0)


def test_decode_steps_are_the_most_run_instruction_of_each_program():
    mods = {"jit_decode_chunk_fn(1)": {"count": 3, "seconds": 0.48,
                                       "events": [],
                                       "max_op_count": {"a": 32, "b": 16}},
            "jit_prefill(2)": {"count": 1, "seconds": 1.0, "events": [],
                               "max_op_count": {"c": 5}}}
    ctx = _ctx(trace={"devices": [{"modules": mods}]},
               live={"slots": 20.0, "kv_rows": 120000.0})
    assert mlr.decode_steps(ctx) == 48
    assert _reader("mixed_latent_decode_step_dev_ms")(ctx) == pytest.approx(10.0)
    need = plugins.load("opcount", "mixed_latent_decode_chunk").count(
        ctx["config"], 20.0, 120000.0)
    share = _reader("mixed_latent_decode_roofline")(ctx)
    assert share == pytest.approx(100 * need["bytes"] / 819e9 / 0.010, rel=1e-6)
    assert 0 < share < 100


def test_the_prefill_reader_pairs_spans_with_modules():
    pairs = [{"real": 10000, "module_s": 0.9}, {"real": 1500, "module_s": 0.1},
             {"real": 0, "module_s": 0.5}]
    ctx = _ctx()
    ctx["_spans"] = {"prefills": pairs}
    count = plugins.load("opcount", "mixed_latent_prefill").count
    least = sum(max(count(ctx["config"], n)["flops"] / 197e12,
                    count(ctx["config"], n)["bytes"] / 819e9)
                for n in (10000, 1500))
    assert _reader("mixed_latent_prefill_roofline")(ctx) == pytest.approx(
        100 * least / 1.0, rel=1e-6)


def test_the_window_kernels_events_are_found_by_name_and_sized_by_their_shapes():
    from benchmark import trace_reduce as tr

    lines = {tr.OP_LINE: [
        ("%window_latent_decode_attention.12 = bf16[32,64,1024]{2,1,0} "
         "custom-call(s32[1]{0} %a, s32[32]{0} %n, bf16[32,64,1152]{2,1,0} %q,"
         " bf16[32,1,1152]{2,1,0} %new, bf16[3,32,512,1152]{3,2,1,0} %c)",
         0.0, 0.0002),
        ("%window_latent_decode_attention.13 = bf16[32,64,1024]{2,1,0} "
         "custom-call(s32[1]{0} %a)", 1.0, 0.0003),
        ("%sparse_decode_attention.3 = (bf16[32,128,512]{2,1,0}, "
         "s32[32,1,128]{2,1,0}) custom-call(s32[1]{0} %a)", 2.0, 0.0004),
        ("%fusion.9 = bf16[32,64,1024]{2,1,0} fusion(bf16[3,32,512,1152] %c)",
         3.0, 0.001)]}
    calls = mlr.reduce_calls(lines)
    assert calls == [[0.0002, 32, 64, 1024], [0.0003, 32, 64, 1024]]
    ctx = _ctx(live={"slots": 20.0, "kv_rows": 100000.0})
    ctx["_mixed_latent_calls"] = calls
    # 20 slots of full rings: 20 x 513 rows a call
    ctx["capture"] = {"metrics_before": {mlr.READ: [({}, 0.0)]},
                      "metrics_after": {mlr.READ: [({}, 2 * 20 * 513.0)]}}
    share = _reader("window_latent_attention_roofline")(ctx)
    one = 2 * (20 * 512 * 1088 + 20 * 64 * (1088 + 1024) + 20 * 1088) / 819e9
    assert share == pytest.approx(100 * 2 * one / 0.0005, rel=1e-6)
    assert 0 < share < 100


# --- a whole run of a tiny cell ----------------------------------------------

def _add_the_family(root):
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(FAMILY, kind), root / "bench" / kind,
                        dirs_exist_ok=True)
    harness._add_entries(root, configs=[{
        "name": "tiny-mixed-latent-moe", "source": "test",
        "file": "bench/configs/tiny-mixed-latent-moe.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "mixed-latent.notes", "config":
                    "tiny-mixed-latent-moe", "traffic": "tiny-notes",
                    "chips": 1, "why": "test"}])


@pytest.mark.parametrize("altered", [False, True])
def test_the_family_is_launched_served_and_checked_through_both_caches(
        altered, tmp_path, monkeypatch, capsys):
    """``launchers/mixed_latent_moe.py`` and ``reference/mixed_latent_moe.py``
    under ``benchmark/`` serve a configuration beside the fixtures: prompts of
    12-100 tokens against a window of 7 (a ring of 16 rows) and a selection of
    8, through ServingCell and the engine's own programs; the sound run is
    correct, an altered token is not; and the cell's own ``/metrics`` text
    holds what the readers without a list of cells read."""
    from kukeon_tpu.obs import expo
    from kukeon_tpu.runtime import serving_cell as sc
    from kukeon_tpu.serving import engine as eng

    root, before = harness._copy_of_the_fixtures(tmp_path)
    _add_the_family(root)
    monkeypatch.setattr(sc, "MODELS", dict(sc.MODELS))
    if altered:
        emit = eng.ServingEngine._emit
        monkeypatch.setattr(
            eng.ServingEngine, "_emit", lambda self, req, token: emit(
                self, req, (int(token) + 1) % self.cfg.vocab_size))
    spec = run.load_cell(str(root), "mixed-latent.notes")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
        held = child.engine._cache_shapes().held
        text = expo.render(child.engine.registry)
    finally:
        child.close()
    said = capsys.readouterr().out
    assert isinstance(child.engine.cfg, slm.SparseLatentMoEConfig)
    assert child.engine.cfg.layers_of(slm.SLIDING) == (2, 3)
    assert {k: v.shape for h in held for k, v in h.items()} == {
        "kidx": (2, 4, 128, 16), "ckv": (2, 4, 128, 128),
        "wckv": (2, 4, 16, 128)}
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert "have a router near-tie" in said
    if altered:
        assert out["correct"] is False
        assert out["checks"]["reference"] is False, said
        return
    assert inproc.sound(out), said
    assert out["compared"]["gap_max"]["value"] < 0.01
    harness._nothing_that_was_there_changed(before)
    # the cell's /metrics text, against an empty scrape at the window's open
    ctx = _ctx(metrics_open={}, metrics_close=stats.parse_prometheus(text))
    for name in ("decode_kv_read_share", "request_queued_ms",
                 "request_prefill_ms", "request_decode_ms_per_token",
                 "queue_wait_p90_ms", "window_latent_rows_read_share",
                 "mixed_latent_held_experts_reached_share"):
        value = _reader(name)(ctx)
        assert value is not None and value >= 0, name
    assert 0 < _reader("window_latent_rows_read_share")(ctx) < 100
    assert 'kukeon_engine_kv_rows{kind="window_latent"}' in text
    assert 'kukeon_engine_kv_rows{kind="latent"}' in text
