"""The ``ssm_moe`` family's benchmark files: the configuration against the
catalog's row, the weights the reference defines against the program's draw,
the operation counts against numbers worked by hand from the published sizes,
the readers on made-up captures, and a whole run of a tiny cell in this
process."""

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
from benchmark.layer_metrics import _ssm_moe as smr
from kukeon_tpu.models import ssm_moe as sm

FAMILY = os.path.join(inproc.FIXTURES, "ssm-moe")
CONFIG = "granite-4.0-h-small-ep2-bf16"
CELL = "granite4h-ep2.retrieval"
ref = plugins.load("reference", "ssm_moe")
launcher = plugins.load("launchers", "ssm_moe")
METRICS = ("ssm_moe_decode_step_dev_ms", "ssm_moe_decode_roofline",
           "ssm_moe_prefill_roofline", "ssd_scan_roofline",
           "ssm_moe_held_hits_per_token", "ssm_moe_state_live_share")


def _file(kind, name):
    with open(os.path.join(plugins.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FAMILY, "configs", "tiny-ssm-moe.json")) as f:
        return json.load(f)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "granite-4.0-h-small")


# --- the configuration file, the manifest and the mix --------------------------

def test_the_file_holds_every_key_of_the_catalog_row_and_cuts_no_width():
    cfg, row = _file("configs", CONFIG), _catalog_row()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size",
        "max_position_embeddings"])
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_local_experts"] == 72
    assert cfg["published"]["vocab_size"] == 100352 == 2 * cfg["vocab_size"]
    # one whole period: the first ten published layers
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert (cfg["router_experts"], cfg["experts_held"]) == (72, [0, 36])
    # the floors: a whole period, at least 8 experts, an eighth of the rows
    assert cfg["num_local_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for width in ("hidden_size", "intermediate_size", "mamba_d_head",
                  "shared_intermediate_size", "mamba_d_state", "mamba_n_heads",
                  "mamba_d_conv", "mamba_chunk_size", "num_experts_per_tok",
                  "num_attention_heads", "num_key_value_heads",
                  "attention_multiplier", "embedding_multiplier",
                  "residual_multiplier", "logits_scaling"):
        assert cfg[width] == row["config"][width], width
    s = cfg["serving"]
    assert (s["num_slots"], s["max_seq_len"], s["chips"], s["decode_chunk"],
            s["max_pending"]) == (32, 8192, 1, 16, 64)
    assert s["max_seq_len"] == cfg["max_position_embeddings"]
    assert len(cfg["assumed"]) >= 8 and "TWO-chip" in cfg["deployment"]
    program = launcher.program_config(cfg)
    assert (program.num_layers, program.num_mixers, program.runs,
            program.d_inner, program.conv_dim) == (10, 9, (5, 4), 8192, 8448)
    assert (program.num_experts, program.experts_held,
            program.experts_per_token) == (72, (0, 36), 10)
    assert program.attention_multiplier == 1 / 128
    assert [(k.name, k.rows) for k in program.cache_kinds(8192)] == [
        ("state", 0), ("full", 8192)]


def test_the_manifest_gains_one_configuration_one_cell_and_six_metrics():
    bench = _file("..", "BENCHMARK")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "retrieval", 1)
    assert CONFIG in [c["name"] for c in bench["configs"]]
    rate = _file("traffic", "retrieval")["params"]["rate_per_s"]
    assert f"Poisson at {rate} req/s" in entry["why"]
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "latency_mean_ms"
        assert ("roofline" in m["name"]) == (m["unit"] == "%"
                                             and m["source"] == "device_trace"
                                             and m["layer"] == "ops")
    spec = run.load_cell(plugins.REPO, CELL)
    reported = {m["name"] for m in spec["per_layer"]}
    assert set(METRICS) <= reported
    # the metrics without a list, and the stalled share it was appended to
    assert {"ttft_p90_ms", "tpot_p90_ms", "queue_wait_p90_ms",
            "device_idle_share", "decode_chunk_steps_mean",
            "decode_kv_read_share", "request_queued_ms", "request_prefill_ms",
            "request_decode_ms_per_token",
            "decode_stalled_by_prefill_share"} <= reported
    assert {m["name"] for m in spec["end_to_end"]} == {
        "latency_mean_ms", "slo_share", "setup_s"}


@pytest.mark.parametrize("key, value", [
    ("mamba_n_groups", 8), ("tie_word_embeddings", False),
    ("mamba_proj_bias", True), ("position_embedding_type", "rope"),
    ("num_local_experts", 72),
    ("layer_types", ["mamba"] * 4 + ["attention"] * 2 + ["mamba"] * 4)])
def test_the_launcher_refuses_keys_the_program_cannot_state(key, value):
    cfg = {**_file("configs", CONFIG), key: value}
    with pytest.raises(SystemExit, match="cannot state"):
        launcher.program_config(cfg)


def test_the_mix_offers_long_prompts_and_answers_of_hundreds_inside_the_context():
    mix, cfg = _file("traffic", "retrieval"), _file("configs", CONFIG)
    gen = plugins.load("generators", mix["generator"]).Generator(
        mix["params"], 5, cfg["vocab_size"], 51.0)
    reqs = gen.arrivals()
    prompts = np.array([len(r["prompt"]) for r in reqs])
    answers = np.array([r["max_new_tokens"] for r in reqs])
    assert 512 <= prompts.min() and prompts.max() <= 7168
    assert 32 <= answers.min() and answers.max() <= 768
    assert 2000 < np.median(prompts) < 3200 and 140 < np.median(answers) < 260
    assert (prompts + answers).max() < cfg["serving"]["max_seq_len"]
    assert max(max(r["prompt"]) for r in reqs) < cfg["vocab_size"]
    assert all(r["prefix_id"] is None for r in reqs)
    from kukeon_tpu.serving.engine import bucket_length
    assert {bucket_length(n) for n in prompts} <= set(mix["warmup"]["prefill"])
    assert mix["warmup"]["prefill"] == [512, 1024, 2048, 4096, 8192]
    assert mix["warmup"]["decode_chunk"] == [1, 4, 16]
    assert mix["limits"] == {"ttft_ms": 2000.0, "tpot_ms": 60.0}
    assert mix["drain_s"] == 30.0 and mix["check"]["requests"] <= 6
    assert len(reqs) == round(mix["params"]["rate_per_s"] * 51)


# --- the weights ---------------------------------------------------------------

@pytest.mark.parametrize("seed,dtype", [(0, "float32"), (2147483000, "bfloat16")])
def test_the_program_draws_the_weights_the_benchmark_defines(seed, dtype):
    """Leaf for leaf: every leaf of the program's tree, at the first mixer,
    the first attention layer and the last layer, against the reference's own
    draw under the same key (seed, the leaf's index in LEAVES, the layer's
    number in the model, the expert's number among all the router scores)."""
    cfg = {**_tiny(), "torch_dtype": dtype}
    program = launcher.program_config(cfg)
    params = sm.init_params(jax.random.key(seed), program)
    root = jax.random.key(seed)
    dt = getattr(jnp, dtype)
    d = ref.dims(cfg)
    H, Im, Is, E, N, K = d["H"], d["Im"], d["Is"], d["E"], d["N"], d["K"]
    MH, I = d["MH"], d["MH"] * d["P"]
    C = I + 2 * N
    Q, KV = d["NH"] * d["D"], d["NKV"] * d["D"]
    first, count = d["first"], d["count"]

    def same(got, want, held_in=dtype):
        """Equal, but for the last float32 bit where two compilations fuse
        the scale into the draw differently (in bfloat16: a rounding tie
        that bit decides, at most one value in 10^3, by one step)."""
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert got.shape == want.shape
        if held_in == "float32":
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_allclose(got, want, rtol=2 ** -7)
            assert (got != want).mean() <= 2e-3

    def mat(name, shape, fan_in, layer=None, scale=1.0, expert=None):
        return ref._matrix(ref._key(root, name, layer, expert), shape, fan_in,
                           "f32", dt, scale)

    def gain(name, shape, layer=None, times=1.0):
        return ref._gain(ref._key(root, name, layer), shape, dt, times)

    def experts(name, shape, fan_in, layer):
        return np.stack([mat(name, shape, fan_in, layer, expert=e)
                         for e in range(first, first + count)])

    same(params["embed"], mat("embed", (cfg["vocab_size"], H), H,
                              scale=1 / 12))
    same(params["final_norm"], gain("final_norm", (H,), times=16.0 * 12))
    shared = {
        "norm1": lambda l: gain("norm1", (H,), l),
        "norm2": lambda l: gain("norm2", (H,), l),
        "s_gate": lambda l: mat("s_gate", (H, Is), H, l),
        "s_up": lambda l: mat("s_up", (H, Is), H, l),
        "s_down": lambda l: mat("s_down", (Is, H), Is, l),
        "e_gate": lambda l: experts("e_gate", (H, Im), H, l),
        "e_up": lambda l: experts("e_up", (H, Im), H, l),
        "e_down": lambda l: experts("e_down", (Im, H), Im, l)}
    mamba = {**shared,
             "w_in": lambda l: mat("w_in", (H, I + C), H, l) * np.where(
                 np.arange(I + C) >= 2 * I, ref.BC_SCALE, 1.0),
             "w_dt": lambda l: mat("w_dt", (H, MH), H, l, ref.DT_SCALE),
             "conv_w": lambda l: mat("conv_w", (C, K), K, l).T,
             "mixer_norm": lambda l: gain("mixer_norm", (I,), l),
             "w_out": lambda l: mat("w_out", (I, H), I, l)}
    wide = d["D"] ** 0.25
    attn = {**shared,
            "wq": lambda l: mat("wq", (H, Q), H, l, wide),
            "wk": lambda l: mat("wk", (H, KV), H, l, wide),
            "wv": lambda l: mat("wv", (H, KV), H, l),
            "wo": lambda l: mat("wo", (Q, H), Q, l)}
    types = cfg["layer_types"]
    for layer in (0, 1, 7):
        w = params["layers"][layer]
        draws = mamba if types[layer] == "mamba" else attn
        for name, draw in draws.items():
            same(w[name], draw(layer))
        assert w["router"].dtype == jnp.float32
        same(w["router"], ref._matrix(ref._key(root, "router", layer), (H, E),
                                      H, "f32", jnp.float32), "float32")
        if types[layer] != "mamba":
            assert set(w) == set(attn) | {"router"}
            continue
        assert set(w) == set(mamba) | {"router", "conv_b", "b_dt", "a_log",
                                       "d_skip"}
        same(w["conv_b"], (ref.CONV_BIAS_STD * jax.random.normal(
            ref._key(root, "conv_b", layer), (C,), jnp.float32)
                           ).astype(dt).astype(jnp.float32))
        same(w["b_dt"], ref._dt_bias(ref._key(root, "b_dt", layer), (MH,)),
             "float32")
        same(np.exp(np.asarray(w["a_log"])), jax.random.uniform(
            ref._key(root, "a_log", layer), (MH,), jnp.float32, ref.A_MIN,
            ref.A_MAX), "float32")
        assert (np.asarray(w["d_skip"]) == 1).all()
    assert ref.LEAVES == sm.LEAVES
    # the recipe leaves the state a long memory: a step's decay by head
    w = params["layers"][0]
    step = jax.nn.softplus(w["b_dt"])
    assert 0.9e-3 < float(step.min()) and float(step.max()) < 1.1e-1
    assert 1.0 <= float(jnp.exp(w["a_log"]).min())
    assert float(jnp.exp(w["a_log"]).max()) <= 16.0


@pytest.mark.parametrize("precision,least", [("a8", 0.005), ("w4", 0.3)])
def test_lower_precision_moves_the_logits(precision, least):
    cfg = _tiny()
    toks = np.random.default_rng(3).integers(0, 384, 60).astype(np.int32)
    at = [np.arange(20, 59)]
    full = ref.logits_at(cfg, 3, [toks], at, 64)[0]
    low = ref.logits_at(cfg, 3, [toks], at, 64, precision=precision)[0]
    assert np.abs(low - full).max() > least


# --- opcount: hand arithmetic at the published widths ---------------------------

MIXER = 4096 * (8192 + 8448 + 128) + 8192 * 4096        # in_proj, out_proj
ATTN = 4096 * (4096 + 2 * 1024) + 4096 * 4096
SHARED = 3 * 4096 * 1536
EXPERT = 3 * 4096 * 768
ROUTER = 4096 * 72
HEAD = 4096 * 50176
DENSE = 9 * MIXER + ATTN + 10 * SHARED + HEAD
SMALL = 9 * (2 * (8448 * 4 + 8448 + 8192) + 4 * 3 * 128) + 2 * 21 * 4096


def test_the_weights_are_9_52_gb_and_32_slots_2_30_gb():
    assert MIXER / 1e6 == pytest.approx(102.2, abs=0.1)
    assert ATTN / 1e6 == pytest.approx(41.9, abs=0.1)
    total = DENSE + 10 * (36 * EXPERT + ROUTER)
    assert total / 1e9 == pytest.approx(4.76, abs=0.01)
    assert (2 * total + 4 * 10 * ROUTER) / 1e9 == pytest.approx(9.52, abs=0.02)
    slot = 9 * (128 * 8192 * 4 + 3 * 8448 * 2) + 2 * 8192 * 1024 * 2
    assert slot / 1e6 == pytest.approx(71.8, abs=0.1)
    assert 32 * slot / 1e9 == pytest.approx(2.30, abs=0.01)


def test_one_scan_call_counts_matrix_operations_and_its_streams_once():
    oc = plugins.load("opcount", "ssd_scan")
    need = oc.count(8192, 128, 64, 128)
    pairs = 257 / 2
    per_token = 2 * (128 * pairs + 8192 * pairs + 2 * 128 * 8192)
    assert per_token / 1e6 == pytest.approx(6.33, abs=0.01)
    assert need["flops"] == pytest.approx(per_token * 8192)
    assert need["bytes"] == (2 * (2 * 8192 * 8192 + 2 * 8192 * 128)
                             + 4 * 8192 * 128 + 4 * 8192 * 128)
    # never an [S, heads, 64, 128] array: 34 GB in float32 at S = 8192
    assert need["bytes"] < 8192 * 128 * 64 * 128 * 4 / 100
    # a prompt shorter than a chunk is one smaller block
    short = oc.count(64, 128, 64, 128)
    assert short["flops"] == pytest.approx(
        64 * 2 * (128 * 32.5 + 8192 * 32.5 + 2 * 128 * 8192))
    assert 0.25e-3 < need["flops"] / 197e12 < 0.35e-3
    assert 0.30e-3 < need["bytes"] / 819e9 < 0.36e-3


def test_a_decode_step_at_20_active_slots():
    cfg = _file("configs", CONFIG)
    oc = plugins.load("opcount", "ssm_moe_decode_chunk")
    s = oc.shapes(cfg)
    assert (s["mixer"], s["attn"], s["shared"], s["expert"], s["router"]) == (
        MIXER, ATTN, SHARED, EXPERT, ROUTER)
    assert (s["n_mixer"], s["n_attn"], s["I"], s["N"], s["C"]) == (
        9, 1, 8192, 128, 8448)
    assert oc.dense_weights(s) == DENSE
    assert (s["scan_state"], s["tail"]) == (4 << 20, 3 * 8448 * 2)
    # at even routing 20 tokens reach 34.2 of the 36 held experts a layer
    reached = 36 * (1 - (1 - 10 / 72) ** 20)
    assert oc.distinct_held(s, 20) == pytest.approx(reached)
    assert reached == pytest.approx(34.2, abs=0.1)
    need = oc.count(cfg, 20, 20 * 3000)
    experts = 10 * (2 * EXPERT * reached + 4 * ROUTER)
    state = 2 * 20 * 9 * ((4 << 20) + 3 * 8448 * 2)
    kv = 2 * 1024 * 2 * (20 * 3000 + 20)
    assert state / 1e9 == pytest.approx(1.53, abs=0.01)
    assert need["bytes"] == pytest.approx(
        2 * DENSE + SMALL + experts + state + kv + 20 * 4096 * 2)
    assert need["bytes"] / 1e9 == pytest.approx(10.95, abs=0.05)
    hits = 20 * 10 * 36 / 72
    assert need["flops"] == pytest.approx(
        2 * 20 * DENSE + 10 * 2 * (EXPERT * hits + ROUTER * 20)
        + 7 * 20 * 9 * 8192 * 128 + 4 * 4096 * 20 * 3000)
    # the state of ACTIVE slots only: an idle program is its weights
    idle = oc.count(cfg, 0, 0)
    assert idle["bytes"] == pytest.approx(2 * DENSE + SMALL + 40 * ROUTER)
    t_bytes = need["bytes"] / 819e9
    assert t_bytes > 20 * need["flops"] / 197e12        # bytes bound it
    assert 0.0130 < t_bytes < 0.0137                    # 13.4 ms a step


def test_an_8192_token_prefill():
    cfg = _file("configs", CONFIG)
    pre = plugins.load("opcount", "ssm_moe_prefill")
    scan = plugins.load("opcount", "ssd_scan").count(8192, 128, 64, 128)
    need = pre.count(cfg, 8192)
    per_token = 9 * MIXER + ATTN + 10 * SHARED
    routed = 10 * 2 * (EXPERT * 8192 * 10 * 36 / 72 + ROUTER * 8192)
    attn = 4 * 4096 * 8192 * 8193 / 2
    assert need["flops"] == pytest.approx(
        2 * 8192 * per_token + routed + attn + 9 * scan["flops"] + 2 * HEAD)
    assert need["flops"] / 1e12 == pytest.approx(27.7, abs=0.1)
    # a prompt's tokens reach every held expert: the stacks are read whole
    assert need["bytes"] == pytest.approx(
        2 * DENSE + 10 * (2 * EXPERT * 36 + 4 * ROUTER)
        + 2 * 1024 * 2 * 8192 + 9 * ((4 << 20) + 3 * 8448 * 2), rel=1e-6)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9    # 140 ms by flops
    assert 0.135 < need["flops"] / 197e12 < 0.145
    # the scans are 1.7% of a prefill's matrix operations
    assert 0.01 < 9 * scan["flops"] / need["flops"] < 0.03


# --- readers -------------------------------------------------------------------

def _ctx(**over):
    cfg = _file("configs", CONFIG)
    ctx = {"config": cfg, "records": [],
           "capture": {"requested": 12.0, "duration_s": 3.0},
           "live": {"slots": 20.0, "kv_rows": 20 * 3000.0},
           "pkg_dir": plugins.HERE, "device": {"kind": "TPU v5 lite"},
           "peaks": _file("", "peaks"),
           "metrics_open": {}, "metrics_close": {},
           "trace": {"devices": [{"modules": {
               "jit_decode_chunk_fn": {"count": 10, "seconds": 3.2,
                                       "events": [],
                                       "max_op_count": {"7": 128, "9": 32}},
               "jit_prefill": {"count": 2, "seconds": 0.8, "events": [],
                               "max_op_count": {"3": 4}}}}]}}
    ctx.update(over)
    return ctx


def test_decode_steps_and_the_step_readers():
    """The layers are unrolled: the most-run instruction of each decode
    program runs once a step."""
    ctx = _ctx()
    assert smr.decode_steps(ctx) == 160
    step = plugins.load("layer_metrics", "ssm_moe_decode_step_dev_ms")
    assert step.read(ctx) == pytest.approx(20.0)
    roof = plugins.load("layer_metrics", "ssm_moe_decode_roofline").read(ctx)
    need = plugins.load("opcount", "ssm_moe_decode_chunk").count(
        ctx["config"], 20.0, 20 * 3000.0)
    assert roof == pytest.approx(100 * need["bytes"] / 819e9 / 0.020)
    assert 60 < roof < 70


def test_the_readers_find_nothing_on_another_programs_run():
    """The parent's program, or another family's cell: no decode module, no
    counter, no capture path, no span. None, and no exception."""
    ctx = _ctx(trace={"devices": [{"modules": {}}]}, records=[],
               live={"slots": 0.0, "kv_rows": 0.0}, _spans=None)
    for name in METRICS:
        assert plugins.load("layer_metrics", name).read(ctx) is None, name


def test_the_counter_readers_read_the_windows_deltas():
    ctx = _ctx(
        metrics_open={smr.STATE_STEPS: [({"what": "held"}, 3200.0),
                                        ({"what": "active"}, 1000.0)],
                      smr.TOKENS: [({}, 1000.0)], smr.HITS: [({}, 5100.0)]},
        metrics_close={smr.STATE_STEPS: [({"what": "held"}, 35200.0),
                                         ({"what": "active"}, 17000.0)],
                       smr.TOKENS: [({}, 401000.0)],
                       smr.HITS: [({}, 2045100.0)]})
    live = plugins.load("layer_metrics", "ssm_moe_state_live_share")
    assert live.read(ctx) == pytest.approx(50.0)
    hits = plugins.load("layer_metrics", "ssm_moe_held_hits_per_token")
    assert hits.read(ctx) == pytest.approx(5.1)


def test_the_prefill_roofline_pairs_spans_with_their_modules():
    pairs = [{"real": 700, "padded": 1024, "module_s": 0.070},
             {"real": 6000, "padded": 8192, "module_s": 0.600},
             {"padded": 512}]                 # a span without its counts
    ctx = _ctx(_spans={"spans": {"engine.step": {}}, "prefills": pairs})
    share = plugins.load("layer_metrics", "ssm_moe_prefill_roofline").read(ctx)
    count = plugins.load("opcount", "ssm_moe_prefill").count
    least = sum(max(n["flops"] / 197e12, n["bytes"] / 819e9)
                for n in (count(ctx["config"], 700), count(ctx["config"], 6000)))
    assert share == pytest.approx(100 * least / 0.670)
    assert 0 < share < 100


def test_the_scan_kernel_is_found_by_its_name_and_sized_by_its_result():
    ops = [
        ("%ssd_scan.3 = (bf16[8192,8192]{1,0:T(8,128)(2,1)}, "
         "f32[128,8192]{1,0:T(8,128)}) custom-call(bf16[8192,8192]{1,0} %x, "
         "f32[8,8192,16]{2,1,0} %cum), custom_call_target=\"tpu_custom_call\"",
         1.0, 0.0030),
        ("%ssd_scan.5 = (bf16[512,8192]{1,0}, f32[128,8192]{1,0}) "
         "custom-call(bf16[512,8192]{1,0} %x)", 1.2, 0.0004),
        ("%fusion.6 = bf16[8192,8192]{1,0} fusion(bf16[8192,8192]{1,0} "
         "%ssd_scan_in), kind=kLoop", 1.3, 0.05),
        ("%ssm_state_update.8 = (f32[32,8192]{1,0}, f32[9,32,128,8192]"
         "{3,2,1,0}) custom-call()", 1.4, 0.01),
    ]
    calls = smr.reduce_scans({"XLA Ops": ops})
    assert calls == [[0.0030, 8192, 8192, 128], [0.0004, 512, 8192, 128]]
    ctx = _ctx(_ssm_moe_scans=calls)
    share = plugins.load("layer_metrics", "ssd_scan_roofline").read(ctx)
    count = plugins.load("opcount", "ssd_scan").count
    least = sum(max(n["flops"] / 197e12, n["bytes"] / 819e9)
                for n in (count(8192, 128, 64, 128), count(512, 128, 64, 128)))
    assert share == pytest.approx(100 * least / 0.0034)
    assert 0 < share < 100
    for none in (None, []):
        assert plugins.load("layer_metrics", "ssd_scan_roofline").read(
            _ctx(_ssm_moe_scans=none)) is None


# --- a whole run of a tiny cell ------------------------------------------------

def _add_the_family(root):
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(FAMILY, kind), root / "bench" / kind,
                        dirs_exist_ok=True)
    harness._add_entries(root, configs=[{
        "name": "tiny-ssm-moe", "source": "test",
        "file": "bench/configs/tiny-ssm-moe.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "ssm-moe.retrieval", "config": "tiny-ssm-moe",
                    "traffic": "tiny-retrieval", "chips": 1, "why": "test"}])


@pytest.mark.parametrize("altered", [None, "token", "state"])
def test_the_family_is_launched_served_and_checked(
        altered, tmp_path, monkeypatch, capsys):
    """``launchers/ssm_moe.py`` and ``reference/ssm_moe.py`` under
    ``benchmark/`` serve a configuration beside the fixtures through
    ServingCell and the engine's own programs: prompts of 1-60 tokens (some
    shorter than the convolution, most past a chunk of 8), answers of several
    chunks. The sound run is correct; an altered token is not; and neither is
    a run whose prefill hands on NO scan state (every request then decodes
    from zeros): the check is not blind to the state."""
    from kukeon_tpu.models import kv_kinds
    from kukeon_tpu.runtime import serving_cell as sc
    from kukeon_tpu.serving import engine as eng

    root, before = harness._copy_of_the_fixtures(tmp_path)
    _add_the_family(root)
    monkeypatch.setattr(sc, "MODELS", dict(sc.MODELS))
    if altered == "token":
        emit = eng.ServingEngine._emit
        monkeypatch.setattr(
            eng.ServingEngine, "_emit", lambda self, req, token: emit(
                self, req, (int(token) + 1) % self.cfg.vocab_size))
    if altered == "state":
        insert = kv_kinds.insert
        monkeypatch.setattr(
            kv_kinds, "insert", lambda cache, kinds, block, length, slot:
            insert(cache, kinds, {**block, "ssm": jnp.zeros_like(
                block["ssm"])}, length, slot))
    spec = run.load_cell(str(root), "ssm-moe.retrieval")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capsys.readouterr().out
    assert isinstance(child.engine.cfg, sm.SsmMoEConfig)
    assert child.engine._cache_shapes().held[0]["ssm"].shape == (6, 4, 16, 32)
    assert [x.shape[3] for x in child.engine._cache_shapes().k] == [128]
    assert out["attempted"] >= 10 and out["failed"] == 0
    if altered:
        assert out["correct"] is False, out["compared"]
        assert out["checks"]["reference"] is False, text
    else:
        assert inproc.sound(out), text
        assert out["compared"]["gap_max"]["value"] < 0.01
    harness._nothing_that_was_there_changed(before)


def test_rehearse_compile_builds_the_familys_engine_from_shapes():
    """``rehearse_compile.abstract_engine`` runs unedited; an insert's
    arguments are the leaves of the family's own block (four arrays, where
    ``rehearse_compile.rehearse`` states two K / V blocks: PERF.md section 7
    item 17)."""
    from benchmark import rehearse_compile
    from kukeon_tpu.models import kv_kinds
    from kukeon_tpu.parallel import make_mesh

    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    cfg, eng = rehearse_compile.abstract_engine(_tiny(), mesh, FAMILY)
    assert type(cfg) is sm.SsmMoEConfig and eng.family.name == "ssm_moe"
    state = eng._abstract_state()
    assert {k: v.shape for k, v in state.cache.held[0].items()} == {
        "conv": (6, 3, 4, 64), "ssm": (6, 4, 16, 32)}
    assert [x.shape for x in state.cache.k] == [(2, 4, 2, 128, 16)]
    with jax.set_mesh(mesh):
        lowered = eng._prefill.lower(
            eng._abstract_params, jax.ShapeDtypeStruct((1, 64), jnp.int32), 5,
            jax.random.key(0), jnp.float32(0), jnp.int32(0), jnp.float32(1))
        block = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                 for o in lowered.out_info[1:]]
        assert [b.shape for b in block] == [
            (6, 3, 1, 64), (2, 1, 64, 2, 16), (6, 1, 16, 32),
            (2, 1, 64, 2, 16)]
        assert kv_kinds.names(eng._kinds) == ("conv", "k", "ssm", "v")
        eng._insert.lower(state, *block, 5, 0, jnp.int32(1))
