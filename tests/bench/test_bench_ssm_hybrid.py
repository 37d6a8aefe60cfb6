"""The ``ssm_hybrid`` family's benchmark files: the weights the reference
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
from benchmark.layer_metrics import _ssm_hybrid as shr
from kukeon_tpu.models import ssm_hybrid as sh

FAMILY = os.path.join(inproc.FIXTURES, "ssm-hybrid")
CONFIG = "ai21-jamba2-3b-bf16"
CELL = "jamba2-3b.long-answers"
ref = plugins.load("reference", "ssm_hybrid")
launcher = plugins.load("launchers", "ssm_hybrid")


def _file(kind, name):
    with open(os.path.join(plugins.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FAMILY, "configs", "tiny-ssm-hybrid.json")) as f:
        return json.load(f)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "AI21-Jamba2-3B")


# --- the configuration file and the mix ----------------------------------------

def test_the_file_holds_every_key_of_the_catalog_row_and_cuts_the_context_only():
    cfg, row = _file("configs", CONFIG), _catalog_row()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["published"] == {"max_position_embeddings": 262144}
    assert cfg["serving"]["max_seq_len"] == cfg["max_position_embeddings"] == 4096
    assert cfg["serving"]["num_slots"] == 64 and cfg["serving"]["chips"] == 1
    assert len(cfg["assumed"]) >= 5 and "whole model" in cfg["deployment"]
    program = launcher.program_config(cfg)
    assert (program.num_layers, program.num_mixers, program.runs,
            program.head_dim, program.d_inner) == (28, 26, (7, 6), 128, 5120)
    assert [(k.name, k.rows) for k in program.cache_kinds(4096)] == [
        ("state", 0), ("full", 4096)]
    entry = next(w for w in _file("..", "BENCHMARK")["workloads"]
                 if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "long-answers", 1)


@pytest.mark.parametrize("key", ["num_experts", "tie_word_embeddings",
                                 "mamba_proj_bias", "sliding_window"])
def test_the_launcher_refuses_keys_the_program_cannot_state(key):
    cfg = _file("configs", CONFIG)
    cfg[key] = {"num_experts": 16, "tie_word_embeddings": False,
                "mamba_proj_bias": True, "sliding_window": 4096}[key]
    with pytest.raises(SystemExit, match="cannot state"):
        launcher.program_config(cfg)


def test_the_mix_offers_short_prompts_and_long_answers_inside_the_context():
    mix, cfg = _file("traffic", "long-answers"), _file("configs", CONFIG)
    gen = plugins.load("generators", mix["generator"]).Generator(
        mix["params"], 5, cfg["vocab_size"], 51.0)
    reqs = gen.arrivals()
    prompts = np.array([len(r["prompt"]) for r in reqs])
    answers = np.array([r["max_new_tokens"] for r in reqs])
    assert 32 <= prompts.min() and prompts.max() <= 2048
    assert 64 <= answers.min() and answers.max() <= 1536
    assert 330 < np.median(prompts) < 440 and 450 < np.median(answers) < 580
    assert answers.mean() > prompts.mean() * 0.9    # the ratio turned round
    assert (prompts + answers).max() < cfg["serving"]["max_seq_len"]
    assert all(r["prefix_id"] is None for r in reqs)
    from kukeon_tpu.serving.engine import bucket_length
    buckets = {bucket_length(n) for n in prompts}
    assert buckets == set(mix["warmup"]["prefill"])
    assert mix["warmup"]["decode_chunk"] == [1, 4, 16]
    assert mix["limits"] == {"ttft_ms": 1000.0, "tpot_ms": 60.0}
    assert len(reqs) == round(mix["params"]["rate_per_s"] * 51)


# --- the weights ---------------------------------------------------------------

@pytest.mark.parametrize("seed,dtype", [(0, "float32"), (2147483000, "bfloat16")])
def test_the_program_draws_the_weights_the_benchmark_defines(seed, dtype):
    """Leaf for leaf: every leaf of the program's tree, at the first and at
    the last layer of its stack, against the reference's own draw under the
    same key (seed, the leaf's index in LEAVES, the layer's number in the
    model)."""
    cfg = {**_tiny(), "torch_dtype": dtype}
    program = launcher.program_config(cfg)
    params = sh.init_params(jax.random.key(seed), program)
    root = jax.random.key(seed)
    dt = getattr(jnp, dtype)
    d = ref.dims(cfg)
    H, F, I, N, K, R = d["H"], d["F"], d["I"], d["N"], d["K"], d["R"]
    Q, KV = d["NH"] * d["D"], d["NKV"] * d["D"]

    def same(got, want, held_in=dtype):
        """Equal, but for the last float32 bit where two compilations fuse
        the scale into the draw differently (in bfloat16: a rounding tie
        that bit decides, at most one value in 10^4, by one step)."""
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert got.shape == want.shape
        if held_in == "float32":
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_allclose(got, want, rtol=2 ** -7)
            assert (got != want).mean() <= 1e-3

    def mat(name, shape, fan_in, layer=None, scale=1.0):
        return ref._matrix(ref._key(root, name, layer), shape, fan_in, "f32",
                           dt, scale)

    def gain(name, shape, layer=None):
        return ref._gain(ref._key(root, name, layer), shape, dt)

    same(params["embed"], mat("embed", (cfg["vocab_size"], H), H))
    same(params["final_norm"], gain("final_norm", (H,)))
    mixers = [i for i in range(8) if not ref.is_attention(cfg, i)]
    assert mixers == [0, 2, 3, 4, 6, 7]
    shared = {"norm1": lambda l: gain("norm1", (H,), l),
              "norm2": lambda l: gain("norm2", (H,), l),
              "w_gate": lambda l: mat("w_gate", (H, F), H, l),
              "w_up": lambda l: mat("w_up", (H, F), H, l),
              "w_down": lambda l: mat("w_down", (F, H), F, l)}
    mamba = {**shared,
             "w_in": lambda l: mat("w_in", (H, 2 * I), H, l),
             "conv_w": lambda l: mat("conv_w", (I, K), K, l).T,
             "conv_b": lambda l: ref._conv_bias(
                 ref._key(root, "conv_b", l), (I,), dt),
             "w_x": lambda l: mat("w_x", (I, R + 2 * N), I, l),
             "dt_norm": lambda l: gain("dt_norm", (R,), l),
             "b_norm": lambda l: gain("b_norm", (N,), l),
             "c_norm": lambda l: gain("c_norm", (N,), l),
             "w_dt": lambda l: mat("w_dt", (R, I), R, l, ref.DT_SCALE),
             "w_out": lambda l: mat("w_out", (I, H), I, l)}
    for at in (0, -1):
        for name, draw in mamba.items():
            same(params["mamba"][name][at], draw(mixers[at]))
        same(params["mamba"]["b_dt"][at], ref._dt_bias(
            ref._key(root, "b_dt", mixers[at]), (I,)), held_in="float32")
    # not drawn: S4D-real, state-major in the program
    assert params["mamba"]["a_log"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.exp(np.asarray(params["mamba"]["a_log"])),
        np.broadcast_to(np.arange(1, N + 1)[None, :, None], (6, N, I)),
        rtol=1e-6)
    assert (np.asarray(params["mamba"]["d_skip"]) == 1).all()
    assert set(params["mamba"]) == set(mamba) | {"b_dt", "a_log", "d_skip"}
    attn = {**shared,
            "wq": lambda l: mat("wq", (H, Q), H, l),
            "wk": lambda l: mat("wk", (H, KV), H, l),
            "wv": lambda l: mat("wv", (H, KV), H, l),
            "wo": lambda l: mat("wo", (Q, H), Q, l)}
    assert set(params["attn"]) == set(attn)
    for at, layer in ((0, 1), (1, 5)):
        for name, draw in attn.items():
            same(params["attn"][name][at], draw(layer))
    assert ref.LEAVES == sh.LEAVES
    # the recipe leaves the state a long memory: a step's decay by channel
    step = jax.nn.softplus(params["mamba"]["b_dt"])
    assert 0.9e-3 < float(step.min()) and float(step.max()) < 1.1e-1
    slow, fast = np.exp(-float(step.min())), np.exp(-N * float(step.max()))
    assert slow > 0.998 and fast < 0.5


@pytest.mark.parametrize("precision,least", [("a8", 0.005), ("w4", 0.3)])
def test_lower_precision_moves_the_logits(precision, least):
    cfg = _tiny()
    toks = np.random.default_rng(3).integers(0, 384, 60).astype(np.int32)
    at = [np.arange(20, 59)]
    full = ref.logits_at(cfg, 3, [toks], at, 64)[0]
    low = ref.logits_at(cfg, 3, [toks], at, 64, precision=precision)[0]
    assert np.abs(low - full).max() > least


# --- opcount -------------------------------------------------------------------

MIXER = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
ATTN = 2560 * (2560 + 2 * 128) + 2560 * 2560
MLP = 3 * 2560 * 8192
HEAD = 2560 * 65536


def test_the_weights_are_6_06_gb_and_64_slots_0_86_gb():
    small = 26 * (5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120 + 192)
    total = 26 * MIXER + 2 * ATTN + 28 * MLP + HEAD + 57 * 2560 + small
    assert total / 1e9 == pytest.approx(3.03, abs=0.01)
    assert total * 2 / 1e9 == pytest.approx(6.06, abs=0.02)
    slot = 26 * (5120 * 16 * 4 + 5120 * 3 * 2) + 2 * 2 * 4096 * 128 * 2
    assert slot / 1e6 == pytest.approx(13.5, abs=0.1)
    assert 64 * slot / 1e9 == pytest.approx(0.86, abs=0.01)


def test_the_scan_counts_its_streams_once_and_nine_operations_an_element():
    oc = plugins.load("opcount", "selective_scan")
    need = oc.count(2048, 5120, 16)
    assert need["bytes"] == 2 * (4 * 2048 * 5120 + 2 * 2048 * 16) + 4 * 5120 * 16
    assert need["flops"] == 9 * 2048 * 5120 * 16
    # never an [S, I, N] array: 671 MB in float32 at S = 2048
    assert need["bytes"] < 2048 * 5120 * 16 * 4 / 7
    assert need["bytes"] / 819e9 > need["flops"] / 197e12     # bytes bound it


def test_decode_step_bytes_and_flops():
    cfg = _file("configs", CONFIG)
    oc = plugins.load("opcount", "ssm_hybrid_decode_chunk")
    s = oc.shapes(cfg)
    assert (s["mixer"], s["attn"], s["mlp"]) == (MIXER, ATTN, MLP)
    assert (s["n_mixer"], s["n_attn"], s["I"], s["N"]) == (26, 2, 5120, 16)
    assert oc.matmul_weights(s) == 26 * MIXER + 2 * ATTN + 28 * MLP + HEAD
    # a slot's state of one mixer: 327,680 B of scan state + 30,720 B of tail
    assert s["state_bytes"] == 5120 * 16 * 4 + 5120 * 3 * 2
    idle = oc.count(cfg, 64, 0, 0)
    state = 2 * 64 * 26 * s["state_bytes"]
    assert state / 1e9 == pytest.approx(1.19, abs=0.01)
    assert idle["bytes"] == pytest.approx(
        2 * oc.matmul_weights(s) + state, rel=0.002)
    need = oc.count(cfg, 64, 45, 45 * 900)
    kv = 2 * 2 * 128 * 2 * (45 * 900 + 45)
    assert need["bytes"] == pytest.approx(
        idle["bytes"] + kv + 45 * 2560 * 2)
    assert need["flops"] == pytest.approx(
        2 * 45 * oc.matmul_weights(s) + 26 * 9 * 45 * 5120 * 16
        + 4 * 2 * 2560 * 45 * 900)
    with open(os.path.join(plugins.HERE, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    t_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    assert t_bytes > 5 * need["flops"] / peak["bf16_flops_per_s"]
    assert 0.0085 < t_bytes < 0.0092      # 7.3 GB a step: 8.9 ms by bytes


def test_prefill_flops_count_the_scan_and_two_attention_layers():
    cfg = _file("configs", CONFIG)
    pre = plugins.load("opcount", "ssm_hybrid_prefill")
    need = pre.count(cfg, 2048)
    dense = 2 * 2048 * (26 * MIXER + 2 * ATTN + 28 * MLP)
    scan = 26 * 9 * 2048 * 5120 * 16
    attn = 4 * 2 * 2560 * 2048 * 2049 / 2
    assert need["flops"] == pytest.approx(dense + scan + attn + 2 * HEAD)
    assert need["flops"] / 2048 / 1e9 == pytest.approx(5.8, abs=0.2)
    assert need["bytes"] == pytest.approx(
        2 * (26 * MIXER + 2 * ATTN + 28 * MLP + HEAD)
        + 2 * 2 * 128 * 2 * 2048 + 26 * (5120 * 16 * 4 + 5120 * 3 * 2))
    short = pre.count(cfg, 32)          # a short prompt is bound by bytes
    assert short["bytes"] / 819e9 > short["flops"] / 197e12
    assert need["flops"] / 197e12 > need["bytes"] / 819e9


# --- readers -------------------------------------------------------------------

def _ctx(**over):
    cfg = _file("configs", CONFIG)
    rec = {"prompt_len": 400, "token_times": [10.0 + 0.1 * i
                                              for i in range(101)]}
    ctx = {"config": cfg, "records": [rec, {"token_times": []}],
           "capture": {"requested": 12.0, "duration_s": 3.0},
           "live": {"slots": 40.0, "kv_rows": 40 * 700.0},
           "pkg_dir": plugins.HERE, "device": {"kind": "TPU v5 lite"},
           "peaks": _file("", "peaks"),
           "metrics_open": {}, "metrics_close": {},
           "trace": {"devices": [{"modules": {
               "jit_decode_chunk_fn": {"count": 14, "seconds": 2.4,
                                       "events": [],
                                       "max_op_count": {"7": 14 * 128,
                                                        "9": 14 * 32}},
               "jit_prefill": {"count": 2, "seconds": 0.3, "events": [],
                               "max_op_count": {"3": 14}}}}]}}
    ctx.update(over)
    return ctx


def test_decode_steps_and_the_step_readers():
    ctx = _ctx()
    assert shr.runs_a_step(ctx["config"]) == 14         # 2 periods x 7 mixers
    assert shr.runs_a_step(_tiny()) == 4                # 2 periods x 2
    assert shr.decode_steps(ctx) == 160
    step = plugins.load("layer_metrics", "ssm_hybrid_decode_step_dev_ms")
    assert step.read(ctx) == pytest.approx(15.0)
    roof = plugins.load("layer_metrics", "ssm_hybrid_decode_roofline").read(ctx)
    need = plugins.load("opcount", "ssm_hybrid_decode_chunk").count(
        ctx["config"], 64, 40.0, 40 * 700.0)
    assert roof == pytest.approx(100 * need["bytes"] / 819e9 / 0.015)
    assert 50 < roof < 100


def test_the_readers_find_nothing_on_another_programs_run():
    """The parent's program, or another family's cell: no decode module, no
    counter, no capture path, no span. None, and no exception."""
    ctx = _ctx(trace={"devices": [{"modules": {}}]}, records=[],
               live={"slots": 0.0, "kv_rows": 0.0}, _spans=None)
    for name in ("ssm_hybrid_decode_step_dev_ms", "ssm_hybrid_decode_roofline",
                 "ssm_hybrid_prefill_roofline", "selective_scan_roofline",
                 "ssm_state_live_share"):
        assert plugins.load("layer_metrics", name).read(ctx) is None, name


def test_the_state_share_is_active_over_held_in_the_window():
    fam = shr.STATE_STEPS
    ctx = _ctx(metrics_open={fam: [({"what": "held"}, 6400.0),
                                   ({"what": "active"}, 1000.0)]},
               metrics_close={fam: [({"what": "held"}, 70400.0),
                                    ({"what": "active"}, 45800.0)]})
    reader = plugins.load("layer_metrics", "ssm_state_live_share")
    assert reader.read(ctx) == pytest.approx(70.0)


def test_the_prefill_roofline_pairs_spans_with_their_modules():
    """Real tokens are the dispatch span's ``real``, the time its own
    module's: nothing of the client's first-token times."""
    pairs = [{"real": 300, "padded": 512, "module_s": 0.030},
             {"real": 2000, "padded": 2048, "module_s": 0.120},
             {"padded": 64}]                  # a span without its counts
    ctx = _ctx(_spans={"spans": {"engine.step": {}}, "prefills": pairs})
    share = plugins.load("layer_metrics", "ssm_hybrid_prefill_roofline").read(ctx)
    count = plugins.load("opcount", "ssm_hybrid_prefill").count
    least = sum(max(n["flops"] / 197e12, n["bytes"] / 819e9)
                for n in (count(ctx["config"], 300), count(ctx["config"], 2000)))
    assert share == pytest.approx(100 * least / 0.150)
    assert 0 < share < 100


def test_the_scan_kernel_is_found_by_its_name_and_sized_by_its_result():
    ops = [
        ("%selective_scan.3 = (f32[2048,5,8,128]{3,2,1,0:T(8,128)}, "
         "f32[5,16,8,128]{3,2,1,0:T(8,128)}) custom-call(f32[32768]{0} %b, "
         "f32[32768]{0} %c), custom_call_target=\"tpu_custom_call\"", 1.0, 0.004),
        ("%selective_scan.3 = (f32[64,5,8,128]{3,2,1,0}, f32[5,16,8,128]"
         "{3,2,1,0}) custom-call(f32[1024]{0} %b)", 1.2, 0.0002),
        ("%fusion.6 = f32[2048,5,8,128]{3,2,1,0} fusion(f32[2048,5120]{1,0} "
         "%selective_scan_in), kind=kLoop", 1.3, 0.05),
        ("%decode_attention.8 = bf16[64,1,32,128]{3,2,1,0} custom-call()",
         1.4, 0.01),
    ]
    calls = shr.reduce_scans({"XLA Ops": ops})
    assert calls == [[0.004, 2048, 5120, 16], [0.0002, 64, 5120, 16]]
    ctx = _ctx(_ssm_hybrid_scans=calls)
    share = plugins.load("layer_metrics", "selective_scan_roofline").read(ctx)
    count = plugins.load("opcount", "selective_scan").count
    least = (count(2048, 5120, 16)["bytes"] + count(64, 5120, 16)["bytes"]) / 819e9
    assert share == pytest.approx(100 * least / 0.0042)
    assert 0 < share < 100
    for none in (None, []):
        assert plugins.load("layer_metrics", "selective_scan_roofline").read(
            _ctx(_ssm_hybrid_scans=none)) is None


# --- a whole run of a tiny cell ------------------------------------------------

def _add_the_family(root):
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(FAMILY, kind), root / "bench" / kind,
                        dirs_exist_ok=True)
    harness._add_entries(root, configs=[{
        "name": "tiny-ssm-hybrid", "source": "test",
        "file": "bench/configs/tiny-ssm-hybrid.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "ssm-hybrid.long", "config": "tiny-ssm-hybrid",
                    "traffic": "tiny-long-answers", "chips": 1,
                    "why": "test"}])


@pytest.mark.parametrize("altered", [None, "token", "state"])
def test_the_family_is_launched_served_and_checked(
        altered, tmp_path, monkeypatch, capsys):
    """``launchers/ssm_hybrid.py`` and ``reference/ssm_hybrid.py`` under
    ``benchmark/`` serve a configuration beside the fixtures through
    ServingCell and the engine's own programs: prompts of 1-60 tokens (some
    shorter than the convolution), answers of several chunks. The sound run
    is correct; an altered token is not; and neither is a run whose prefill
    hands on NO scan state (every request then decodes from zeros): the check
    is not blind to the state."""
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
    spec = run.load_cell(str(root), "ssm-hybrid.long")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
    finally:
        child.close()
    text = capsys.readouterr().out
    assert isinstance(child.engine.cfg, sh.SsmHybridConfig)
    assert child.engine._cache_shapes().held[0]["ssm"].shape == (6, 4, 8, 128)
    assert [x.shape[3] for x in child.engine._cache_shapes().k] == [128]
    assert out["attempted"] >= 10 and out["failed"] == 0
    if altered:
        assert out["correct"] is False
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
    assert type(cfg) is sh.SsmHybridConfig and eng.family.name == "ssm_hybrid"
    state = eng._abstract_state()
    assert {k: v.shape for k, v in state.cache.held[0].items()} == {
        "conv": (6, 3, 4, 128), "ssm": (6, 4, 8, 128)}
    assert [x.shape for x in state.cache.k] == [(2, 4, 1, 128, 16)]
    with jax.set_mesh(mesh):
        lowered = eng._prefill.lower(
            eng._abstract_params, jax.ShapeDtypeStruct((1, 64), jnp.int32), 5,
            jax.random.key(0), jnp.float32(0), jnp.int32(0), jnp.float32(1))
        block = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                 for o in lowered.out_info[1:]]
        assert [b.shape for b in block] == [
            (6, 3, 1, 128), (2, 1, 64, 1, 16), (6, 1, 8, 128),
            (2, 1, 64, 1, 16)]
        assert kv_kinds.names(eng._kinds) == ("conv", "k", "ssm", "v")
        eng._insert.lower(state, *block, 5, 0, jnp.int32(1))
