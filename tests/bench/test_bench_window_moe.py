"""The ``window_moe`` family's benchmark files: the weights the reference
defines against the program's draw, the operation counts against numbers
worked by hand from the published sizes, the readers on made-up captures, and
a whole run of a tiny cell in this process."""

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
from benchmark import plugins, run
from benchmark.layer_metrics import _window_moe as wmr
from kukeon_tpu.models import window_moe as wm

FAMILY = os.path.join(inproc.FIXTURES, "window-moe")
CONFIG = "trinity-large-preview-ep8-bf16"
CELL = "trinity-ep8.mixed-lengths"
ref = plugins.load("reference", "window_moe")
launcher = plugins.load("launchers", "window_moe")


def _file(kind, name):
    with open(os.path.join(plugins.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FAMILY, "configs", "tiny-window-moe.json")) as f:
        return json.load(f)


# --- the configuration file --------------------------------------------------

def test_the_file_holds_every_published_width_and_states_its_cut():
    cfg = _file("configs", CONFIG)
    published = {"hidden_size": 3072, "num_attention_heads": 48,
                 "num_key_value_heads": 8, "head_dim": 128,
                 "moe_intermediate_size": 3072, "intermediate_size": 12288,
                 "num_experts_per_tok": 4, "sliding_window": 4096,
                 "num_shared_experts": 1, "route_scale": 2.448,
                 "rope_theta": 10000, "global_attn_every_n_layers": 4}
    assert {k: cfg[k] for k in published} == published
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 256
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    assert cfg["experts_held"] == [0, 32] and cfg["num_experts"] == 32
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg["serving"]["num_slots"] == 32
    assert cfg["serving"]["max_seq_len"] == cfg["max_position_embeddings"] == 8192
    assert len(cfg["assumed"]) >= 6 and "eight" in cfg["deployment"]
    program = launcher.program_config(cfg)
    assert (program.num_unrolled, program.num_periods) == (1, 1)
    assert [(k.name, k.rows, len(k.layers)) for k in
            program.cache_kinds(8192)] == [("window", 4096, 4),
                                           ("full", 8192, 1)]


def test_the_mix_offers_prompts_on_both_sides_of_the_window():
    mix, cfg = _file("traffic", "mixed-lengths"), _file("configs", CONFIG)
    gen = plugins.load("generators", mix["generator"]).Generator(
        mix["params"], 5, cfg["vocab_size"], 51.0)
    reqs = gen.arrivals()
    lens = np.array([len(r["prompt"]) for r in reqs])
    past = lens > cfg["sliding_window"]
    assert 0.35 < past.mean() < 0.45 and lens.min() >= 128
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs) \
        <= cfg["serving"]["max_seq_len"]
    assert all(r["prefix_id"] is None for r in reqs)
    assert all(0 <= t < cfg["vocab_size"] for r in reqs[:3]
               for t in r["prompt"])
    from kukeon_tpu.serving.engine import bucket_length
    buckets = {min(bucket_length(n), 8192) for n in lens}
    assert buckets <= set(mix["warmup"]["prefill"]) and 8192 in buckets


# --- the weights -------------------------------------------------------------

@pytest.mark.parametrize("seed,dtype", [(0, "float32"), (2147483000, "bfloat16")])
def test_the_program_draws_the_weights_the_benchmark_defines(seed, dtype):
    cfg = {**_tiny(), "torch_dtype": dtype}
    program = launcher.program_config(cfg)
    params = wm.init_params(jax.random.key(seed), program)
    root = jax.random.key(seed)
    dt = getattr(jnp, dtype)
    first, count = cfg["experts_held"]
    H, Im = cfg["hidden_size"], cfg["moe_intermediate_size"]

    def same(got, want, held_in=dtype):
        """Equal, but for the last float32 bit where two compilations fuse
        the scale into the draw differently (in bfloat16: a rounding tie
        that bit decides, at most one value in 10^4, by one step)."""
        got, want = np.asarray(got, np.float32), np.asarray(want)
        if held_in == "float32":
            np.testing.assert_allclose(got, want, rtol=1e-6)
        else:
            np.testing.assert_allclose(got, want, rtol=2 ** -7)
            assert (got != want).mean() <= 1e-4

    same(params["embed"], ref._matrix(ref._key(root, "embed"),
                                      (cfg["vocab_size"], H), H, "f32", dt))
    same(params["final_norm"], ref._gain(ref._key(root, "final_norm"), (H,), dt))
    same(params["head"][0]["wq"], ref._matrix(
        ref._key(root, "wq", 0), (H, 64), H, "f32", dt))
    same(params["head"][0]["w_down"], ref._matrix(
        ref._key(root, "w_down", 0), (128, H), 128, "f32", dt))
    # position 2 of period 1 is layer 1 + 1 * 4 + 2 = 7
    layer = params["period"][2]
    same(layer["norm3"][1], ref._gain(ref._key(root, "norm3", 7), (H,), dt))
    same(layer["router"][1], ref._matrix(
        ref._key(root, "router", 7), (H, 16), H, "f32", jnp.float32),
        held_in="float32")
    same(layer["bias"][1], ref.BIAS_STD * jax.random.normal(
        ref._key(root, "bias", 7), (16,), jnp.float32), held_in="float32")
    assert layer["e_gate"].shape == (2, count, H, Im)
    for i in range(count):      # the experts this chip holds, by their number
        same(layer["e_down"][1, i], ref._matrix(
            ref._key(root, "e_down", 7, first + i), (Im, H), Im, "f32", dt))
    assert ref.LEAVES == wm.LEAVES


@pytest.mark.parametrize("precision,least", [("a8", 0.005), ("w4", 0.3)])
def test_lower_precision_moves_the_logits(precision, least, capsys):
    cfg = _tiny()
    toks = np.random.default_rng(3).integers(0, 384, 60).astype(np.int32)
    at = [np.arange(20, 59)]
    full = ref.logits_at(cfg, 3, [toks], at, 64)[0]
    low = ref.logits_at(cfg, 3, [toks], at, 64, precision=precision)[0]
    gap = full.max(-1) - full[np.arange(39), low.argmax(-1)]
    assert gap.max() > least
    assert "sampled positions have a router near-tie" in capsys.readouterr().out


# --- opcount -----------------------------------------------------------------

ATTN = 3072 * (2 * 6144 + 2 * 1024) + 6144 * 3072        # wq wg wk wv wo
EXPERT = 3 * 3072 * 3072
DENSE_MLP = 3 * 3072 * 12288
HEAD = 3072 * 25024


def test_the_weights_are_8_64_gb_and_a_slot_holds_100_7_mb():
    layer = ATTN + EXPERT * 33 + 3072 * 256 * 2      # router counted in bf16
    total = 4 * layer + ATTN + DENSE_MLP + 2 * HEAD
    assert total * 2 / 1e9 == pytest.approx(8.64, abs=0.02)
    slot = (4 * 4096 + 8192) * 2 * 1024 * 2
    assert slot / 1e6 == pytest.approx(100.7, abs=0.1)


def test_decode_step_bytes_and_flops():
    cfg = _file("configs", CONFIG)
    oc = plugins.load("opcount", "window_moe_decode_chunk")
    s = oc.shapes(cfg)
    assert (s["attn"], s["expert"], s["dense_mlp"]) == (ATTN, EXPERT, DENSE_MLP)
    assert (s["n_window"], s["n_full"], s["n_expert"]) == (4, 1, 4)
    whole = 5 * ATTN + DENSE_MLP + 4 * EXPERT + HEAD
    assert oc.whole_weights(s) == whole
    # no slot: the dense weights, the shared experts and the routers alone
    idle = oc.count(cfg, 0, 0, 0)
    assert idle["bytes"] == 2 * whole + 4 * 4 * 3072 * 256
    # a full batch: 32 tokens reach 12.7 of the 32 held experts a layer
    distinct = 32 * (1 - (1 - 4 / 256) ** 32)
    assert oc.distinct_held(s, 32) == pytest.approx(distinct)
    assert 12.6 < distinct < 12.8
    need = oc.count(cfg, 32, 32 * 3000, 32 * 5000)
    kv = 2 * 1024 * 2 * (4 * 32 * 3000 + 32 * 5000 + 5 * 32)
    assert need["bytes"] == pytest.approx(
        idle["bytes"] + 4 * 2 * EXPERT * distinct + kv + 32 * 3072 * 2)
    hits = 32 * 4 * 32 / 256
    assert need["flops"] == pytest.approx(
        2 * 32 * (whole + 4 * 3072 * 256) + 2 * 4 * EXPERT * hits
        + 4 * 6144 * (4 * 32 * 3000 + 32 * 5000))
    with open(os.path.join(plugins.HERE, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    t_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    assert t_bytes > 10 * need["flops"] / peak["bf16_flops_per_s"]
    assert 0.007 < t_bytes < 0.009


def test_prefill_flops_count_a_window_layers_band():
    cfg = _file("configs", CONFIG)
    pre = plugins.load("opcount", "window_moe_prefill")
    assert pre.pairs(1000) == pre.pairs(1000, 4096) == 1000 * 1001 / 2
    assert pre.pairs(8192, 4096) == 4096 * 4097 / 2 + 4096 * 4096
    need = pre.count(cfg, 8192)
    dense = 2 * 8192 * (5 * ATTN + DENSE_MLP + 4 * (EXPERT + 3072 * 256))
    routed = 2 * EXPERT * 4 * (8192 * 4 * 32 / 256)
    attn = 4 * 6144 * (pre.pairs(8192) + 4 * pre.pairs(8192, 4096))
    assert need["flops"] == pytest.approx(dense + routed + attn + 2 * HEAD)
    assert need["bytes"] == pytest.approx(
        2 * (5 * ATTN + DENSE_MLP + HEAD + 4 * 33 * EXPERT)
        + 4 * 4 * 3072 * 256 + 2 * 5 * 1024 * 2 * 8192, rel=1e-6)
    short = pre.count(cfg, 128)         # a short prompt is bound by bytes
    assert short["bytes"] / 819e9 > short["flops"] / 197e12


# --- readers -----------------------------------------------------------------

def _ctx(**over):
    cfg = _file("configs", CONFIG)
    rec = {"prompt_len": 6000, "token_times": [10.0 + 0.1 * i
                                               for i in range(101)]}
    short = {"prompt_len": 1000, "token_times": [12.0, 30.0]}
    ctx = {"config": cfg, "records": [rec, short, {"token_times": []}],
           "capture": {"requested": 12.0, "duration_s": 3.0},
           "pkg_dir": plugins.HERE, "device": {"kind": "TPU v5 lite"},
           "peaks": _file("", "peaks"),
           "metrics_open": {}, "metrics_close": {},
           "trace": {"devices": [{"modules": {
               "jit_decode_chunk_fn": {"count": 10, "seconds": 2.4,
                                       "events": [],
                                       "max_op_count": {"7": 128, "9": 32}},
               "jit_prefill": {"count": 2, "seconds": 0.3, "events": [],
                               "max_op_count": {"3": 2}}}}]}}
    ctx.update(over)
    return ctx


def test_live_rows_by_kind_and_decode_steps():
    ctx = _ctx()
    held = wmr.live(ctx)
    assert held["slots"] == pytest.approx(2.0)
    # the long request holds 6020..6050 rows, its ring 4096; the short 1000
    assert held["full_rows"] == pytest.approx(6035 + 1000.5, abs=1.0)
    assert held["window_rows"] == pytest.approx(4096 + 1000.5, abs=1.0)
    assert wmr.periods(ctx["config"]) == 1
    assert wmr.decode_steps(ctx) == 160
    step = plugins.load("layer_metrics", "window_moe_decode_step_dev_ms")
    assert step.read(ctx) == pytest.approx(15.0)
    roof = plugins.load("layer_metrics", "window_moe_decode_roofline").read(ctx)
    need = plugins.load("opcount", "window_moe_decode_chunk").count(
        ctx["config"], held["slots"], held["window_rows"], held["full_rows"])
    assert roof == pytest.approx(100 * need["bytes"] / 819e9 / 0.015)
    assert 0 < roof < 100


def test_the_readers_find_nothing_on_a_dense_programs_run():
    """The parent's program, or a Mistral cell: no decode module of this
    family's, no counter, no capture path. None, and no exception."""
    ctx = _ctx(trace={"devices": [{"modules": {}}]}, records=[])
    for name in ("window_moe_decode_step_dev_ms", "window_moe_decode_roofline",
                 "window_moe_prefill_roofline", "expert_layer_roofline",
                 "held_expert_hit_share"):
        assert plugins.load("layer_metrics", name).read(ctx) is None, name


def test_hit_share_is_hits_over_routed_in_the_window():
    ctx = _ctx(metrics_open={wmr.ROUTED: [({}, 1000.0)], wmr.HITS: [({}, 100.0)]},
               metrics_close={wmr.ROUTED: [({}, 9000.0)],
                              wmr.HITS: [({}, 1100.0)]})
    reader = plugins.load("layer_metrics", "held_expert_hit_share")
    assert reader.read(ctx) == pytest.approx(12.5)


def test_the_expert_layers_operations_are_found_by_their_operand_shapes():
    cfg = _file("configs", CONFIG)
    patterns = wmr.stack_patterns(cfg)
    mod = ("jit_decode_chunk_fn(7)", 1.0, 1.0)
    ops = [
        ("%ragged-dot-none.1 = bf16[128,3072]{1,0} custom-call(s32[1] %a, "
         "bf16[128,3072]{1,0} %x, bf16[32,3072,3072]{2,1,0} %w)", 1.0, 0.10),
        ("%fusion.5 = bf16[32,1,3072]{2,1,0} fusion(bf16[32,1,3072]{2,1,0} "
         "%h, bf16[1,3072,3072]{2,1,0} %s_gate), kind=kOutput", 1.2, 0.02),
        ("%fusion.6 = bf16[32,1,6144]{2,1,0} fusion(bf16[32,1,3072]{2,1,0} "
         "%h, bf16[3072,6144]{1,0} %wq), kind=kOutput", 1.3, 0.05),
        ("%fusion.9 = bf16[128,3072]{1,0} fusion(bf16[4,32,3072,3072]{3,2,1,0}"
         " %e_up), kind=kLoop", 5.0, 0.5),       # outside the decode module
    ]
    got = wmr.reduce_ops({"XLA Modules": [mod], "XLA Ops": ops}, patterns)
    assert got["routed_s"] == pytest.approx(0.10)
    assert got["shared_s"] == pytest.approx(0.02)
    assert len(got["ops"]) == 2
    # an expert as wide as the query: nothing to tell the matrices apart by
    assert wmr.stack_patterns({**cfg, "moe_intermediate_size": 6144}) is None
    ctx = _ctx(_window_moe_ops={"routed_s": 0.8, "shared_s": 0.16, "ops": {}})
    share = plugins.load("layer_metrics", "expert_layer_roofline").read(ctx)
    oc = plugins.load("opcount", "window_moe_decode_chunk")
    need = oc.expert_layer(oc.shapes(cfg), 2.0)
    assert share == pytest.approx(
        100 * 4 * need["bytes"] / 819e9 * 160 / 0.96)
    ctx = _ctx(_window_moe_ops=None)
    assert plugins.load("layer_metrics",
                        "expert_layer_roofline").read(ctx) is None


# --- a whole run of a tiny cell ----------------------------------------------

def _add_the_family(root):
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(FAMILY, kind), root / "bench" / kind,
                        dirs_exist_ok=True)
    harness._add_entries(root, configs=[{
        "name": "tiny-window-moe", "source": "test",
        "file": "bench/configs/tiny-window-moe.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "window-moe.mixed", "config": "tiny-window-moe",
                    "traffic": "tiny-mixed-lengths", "chips": 1,
                    "why": "test"}])


@pytest.mark.parametrize("altered", [False, True])
def test_the_family_is_launched_served_and_checked_past_its_window(
        altered, tmp_path, monkeypatch, capsys):
    """``launchers/window_moe.py`` and ``reference/window_moe.py`` under
    ``benchmark/`` serve a configuration beside the fixtures: prompts of 30-42
    tokens against a window of 8, through ServingCell and the engine's own
    programs; the sound run is correct, an altered token is not."""
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
    spec = run.load_cell(str(root), "window-moe.mixed")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capsys.readouterr().out
    assert isinstance(child.engine.cfg, wm.WindowMoEConfig)
    assert [x.shape[3] for x in child.engine._cache_shapes().k] == [8, 128]
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert "sampled positions have a router near-tie" in text
    if altered:
        assert out["correct"] is False
        assert out["checks"]["reference"] is False, text
    else:
        assert inproc.sound(out), text
        assert out["compared"]["gap_max"]["value"] < 0.01
    harness._nothing_that_was_there_changed(before)


def test_rehearse_compile_builds_the_familys_engine_from_shapes(tmp_path):
    from benchmark import rehearse_compile
    from kukeon_tpu.parallel import make_mesh

    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    cfg, eng = rehearse_compile.abstract_engine(_tiny(), mesh, FAMILY)
    assert type(cfg) is wm.WindowMoEConfig and eng.family.name == "window_moe"
    state = eng._abstract_state()
    assert [x.shape for x in state.cache.k] == [(7, 4, 2, 8, 16),
                                                (2, 4, 2, 128, 16)]
    kv = jax.ShapeDtypeStruct((9, 1, 64, 2, 16), jnp.float32)
    with jax.set_mesh(mesh):
        eng._insert.lower(state, kv, kv, 5, 0, jnp.int32(1))
