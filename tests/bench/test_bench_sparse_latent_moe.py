"""The ``sparse_latent_moe`` family's benchmark files: the weights the
reference defines against the program's draw, the selection bias that gives
every seed the same load, the operation counts against numbers worked by hand
from the published sizes, the readers on made-up captures, and a whole run of
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
from benchmark import plugins, run
from benchmark.layer_metrics import _sparse_latent as slr
from kukeon_tpu.models import sparse_latent_moe as slm

FAMILY = os.path.join(inproc.FIXTURES, "sparse-latent-moe")
CONFIG = "deepseek-v3.2-exp-ep16-bf16"
CELL = "deepseek-v32-ep16.long-context"
ref = plugins.load("reference", "sparse_latent_moe")
launcher = plugins.load("launchers", "sparse_latent_moe")


def _file(kind, name):
    with open(os.path.join(plugins.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FAMILY, "configs",
                           "tiny-sparse-latent-moe.json")) as f:
        return json.load(f)


# --- the configuration file --------------------------------------------------

def test_the_file_holds_every_published_width_and_states_its_cut():
    cfg = _file("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2-Exp")
    assert cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == published
            assert cfg[key] != published
        else:
            assert cfg[key] == published, key
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]]
    assert cfg["router_experts"] == row["config"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    program = launcher.program_config(cfg)
    assert program.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    kind, = program.cache_kinds(32768)
    assert (kind.name, kind.rows, kind.select) == ("latent", 32768, 2048)
    assert dict(kind.arrays) == {"kidx": 128, "ckv": 640}


def test_the_mix_keeps_every_prompt_past_the_selection():
    mix = _file("traffic", "long-context")
    cfg = _file("configs", CONFIG)
    gen = plugins.load("generators", mix["generator"]).Generator(
        mix["params"], 1, cfg["vocab_size"], 51.0)
    lens = [r["new_tokens"] for r in gen.arrivals()]
    assert min(lens) > cfg["index_topk"]
    assert max(lens) + 512 <= cfg["serving"]["max_seq_len"]
    assert sum(n > 16384 for n in lens) >= 1      # the 32768 bucket is used
    assert sum(8192 < n <= 16384 for n in lens) >= 3
    assert mix["warmup"]["prefill"] == [4096, 8192, 16384, 32768]


@pytest.mark.parametrize("seed,dtype", [(0, "float32"),
                                        (2147483000, "bfloat16")])
def test_the_program_draws_the_weights_the_benchmark_defines(seed, dtype):
    cfg = {**_tiny(), "torch_dtype": dtype}
    program = launcher.program_config(cfg)
    params = slm.init_params(jax.random.key(seed), program)
    root = jax.random.key(seed)
    dt = getattr(jnp, dtype)
    first, count = cfg["experts_held"]
    H, Im, Q, R = 64, 48, 48, 32

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
    same(params["final_norm"], ref._gain(ref._key(root, "final_norm"), (H,), dt))
    dense, expert = params["layers"][0], params["layers"][2]
    same(dense["wq_b"], mat("wq_b", (Q, 4 * 24), Q, 0))
    same(dense["w_down"], mat("w_down", (128, H), 128, 0))
    # wkv_b is drawn as published and held as its two halves a head
    both = np.asarray(mat("wkv_b", (R, 4 * 32), R, 2)).reshape(R, 4, 32)
    same(expert["wkv_bk"], both[..., :16].transpose(1, 2, 0))
    same(expert["wkv_bv"], both[..., 16:].transpose(1, 0, 2))
    same(expert["wi_k_shift"], ref._gain(
        ref._key(root, "wi_k_shift", 2), (16,), dt, ref.SHIFT_STD, 0.0))
    same(expert["router"], mat("router", (H, 16), H, 2, dtype=jnp.float32),
         held_in="float32")
    # fitted to the layer's own router and norm, by each side's own code
    fitted = ref.selection_bias(
        ref._key(root, "bias", 2),
        mat("router", (H, 16), H, 2, dtype=jnp.float32),
        ref._gain(ref._key(root, "norm2", 2), (H,), dt), ref.dims(cfg))
    np.testing.assert_allclose(expert["bias"], fitted, atol=2e-5)
    assert np.abs(np.asarray(fitted)).max() > 1e-3
    for i in range(count):      # the experts this chip holds, by their number
        same(expert["e_down"][i], mat("e_down", (Im, H), Im, 2, first + i))
    assert ref.LEAVES == slm.LEAVES


@pytest.mark.parametrize("seed", [0, 1, 7, 2147483000])
def test_the_fitted_bias_gives_every_seed_the_same_load(seed):
    """A Gaussian router's own draw leaves a block of experts a load that the
    seed moves; the bias fitted to it brings every expert to the even load on
    tokens the fit has not seen (normed, of isotropic direction, under the
    layer's gain). 64 experts in 8 groups, 4 kept, top 8; a chip would hold
    8."""
    H, E, N = 128, 64, 1 << 15
    c = {"E": E, "K": 8, "groups": 8, "kept": 4}
    key = jax.random.key(seed)
    router = jax.random.normal(jax.random.fold_in(key, 1), (H, E)) * H ** -0.5
    gain = 1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (H,))
    bias = ref.selection_bias(jax.random.fold_in(key, 3), router, gain, c)
    h = jax.random.normal(jax.random.fold_in(key, 4), (N, H))
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)) * gain
    s = jax.nn.sigmoid(jnp.dot(h, router, precision="highest"))

    def loads(b):
        sel = np.asarray(ref._chosen(s + b, 8, 8, 4)[1][:, :8])
        return np.bincount(sel.reshape(-1), minlength=E) / (N * 8 / E)

    fitted, plain = loads(bias), loads(jnp.zeros(E))
    assert plain.max() - plain.min() > 0.2          # the draw alone is uneven
    assert np.abs(fitted - 1).max() < 0.1           # the fit's own noise, 1.2%
                                                    # an expert, and this sample's
    blocks = fitted.reshape(8, 8).mean(1)           # a chip's share of the load
    assert np.abs(blocks - 1).max() < 0.04


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


def test_the_reference_pads_a_sequence_to_one_of_a_few_sizes():
    assert [ref.padded(n, 32768) for n in (3000, 4096, 4097, 9000, 31000)] \
        == [4096, 4096, 8192, 16384, 32768]
    assert ref.padded(50, 128) == 128


# --- opcount -----------------------------------------------------------------

ATTN = (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168)
INDEXER = 1536 * 8192 + 7168 * 128 + 7168 * 64
EXPERT = 3 * 7168 * 2048
DENSE_MLP = 3 * 7168 * 18432
HEAD = 7168 * 16160


def test_the_weights_are_9_27_gb_and_a_token_holds_7040_bytes():
    layer = ATTN + INDEXER + 17 * EXPERT
    total = 2 * (5 * (ATTN + INDEXER) + DENSE_MLP + 4 * 17 * EXPERT
                 + 2 * HEAD) + 4 * 4 * 7168 * 256
    assert ATTN / 1e6 == pytest.approx(187.1, abs=0.05)
    assert INDEXER / 1e6 == pytest.approx(14.0, abs=0.05)
    assert total / 1e9 == pytest.approx(9.27, abs=0.02)
    assert layer > 0 and 5 * (576 + 128) * 2 == 7040


def test_decode_step_bytes_and_flops():
    count = plugins.load("opcount", "sparse_latent_decode_chunk").count
    cfg = _file("configs", CONFIG)
    got = count(cfg, 4, 40000)           # four slots of 10000 rows
    read = 16 * (1 - (1 - 8 / 256) ** 4)             # held experts a layer reads
    weights = 2 * (5 * (ATTN + INDEXER) + DENSE_MLP + 4 * EXPERT + HEAD
                   + 4 * read * EXPERT) + 4 * 4 * 7168 * 256
    cache = 2 * 5 * (40000 * 128 + 4 * 2048 * 576 + 4 * (128 + 576))
    assert got["bytes"] == pytest.approx(weights + cache + 4 * 7168 * 2)
    # a slot shorter than the selection reads all its rows and no more
    short = count(cfg, 4, 4000)
    assert (got["bytes"] - short["bytes"]) == pytest.approx(
        2 * 5 * (36000 * 128 + (4 * 2048 - 4000) * 576))
    assert got["flops"] > 2 * (5 * (ATTN + INDEXER) + DENSE_MLP) * 4


def test_prefill_flops_count_the_selected_pairs_only():
    count = plugins.load("opcount", "sparse_latent_prefill").count
    pairs = plugins.load("opcount", "sparse_masked_attention").selected_pairs
    cfg = _file("configs", CONFIG)
    assert pairs(0, 4096, 2048) == sum(min(2048, t + 1) for t in range(4096))
    assert pairs(1000, 3000, 2048) == sum(min(2048, t + 1)
                                          for t in range(1000, 4000))
    a, b = count(cfg, 8192), count(cfg, 16384)
    per_token = 2 * (5 * (ATTN + INDEXER) + DENSE_MLP + 4 * EXPERT * 1.5
                     + 4 * 7168 * 256)
    index = 5 * 2 * 64 * 128 * (16384 * 16385 - 8192 * 8193) / 2
    attend = 5 * 2 * 128 * 320 * 8192 * 2048     # rows past 2048 keep 2048
    assert b["flops"] - a["flops"] == pytest.approx(
        per_token * 8192 + index + attend, rel=1e-9)
    assert b["bytes"] - a["bytes"] == 2 * 5 * 8192 * 704


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
    ctx = _ctx()
    ctx["_sparse_latent_calls"] = None
    ctx["_spans"] = None
    for name in ("sparse_latent_decode_step_dev_ms",
                 "sparse_latent_decode_roofline",
                 "sparse_latent_prefill_roofline", "sparse_attention_roofline",
                 "sparse_rows_read_share", "held_hits_per_token"):
        assert _reader(name)(ctx) is None, name


def test_the_counters_readers_divide_the_windows_deltas():
    from benchmark import stats

    open_ = stats.parse_prometheus(
        f"{slr.TOKENS} 100\n{slr.HITS} 40\n{slr.SELECTED} 0\n{slr.LIVE} 0\n")
    close = stats.parse_prometheus(
        f"{slr.TOKENS} 1100\n{slr.HITS} 550\n{slr.SELECTED} 2048\n"
        f"{slr.LIVE} 10240\n")
    ctx = _ctx(metrics_open=open_, metrics_close=close)
    assert _reader("held_hits_per_token")(ctx) == pytest.approx(0.51)
    assert _reader("sparse_rows_read_share")(ctx) == pytest.approx(20.0)


def test_decode_steps_are_the_most_run_instruction_of_each_program():
    mods = {"jit_decode_chunk_fn(1)": {"count": 3, "seconds": 0.48,
                                       "events": [],
                                       "max_op_count": {"a": 32, "b": 16}},
            "jit_prefill(2)": {"count": 1, "seconds": 1.0, "events": [],
                               "max_op_count": {"c": 5}}}
    ctx = _ctx(trace={"devices": [{"modules": mods}]},
               live={"slots": 4.0, "kv_rows": 40000.0})
    assert slr.decode_steps(ctx) == 48
    assert _reader("sparse_latent_decode_step_dev_ms")(ctx) == pytest.approx(10.0)
    need = plugins.load("opcount", "sparse_latent_decode_chunk").count(
        ctx["config"], 4.0, 40000.0)
    assert _reader("sparse_latent_decode_roofline")(ctx) == pytest.approx(
        100 * need["bytes"] / 819e9 / 0.010, rel=1e-6)


def test_the_kernels_events_are_found_by_name_and_sized_by_their_shapes():
    from benchmark import trace_reduce as tr

    lines = {tr.OP_LINE: [
        ("%sparse_select_rows.35 = s8[64,4096,512]{2,1,0} custom-call("
         "s32[1]{0} %a, bf16[4096,8192]{1,0} %b)", 0.0, 0.020),
        ("%sparse_masked_attention.7 = bf16[16,4096,128]{2,1,0} custom-call("
         "s32[1]{0} %a, bf16[16,4096,192]{2,1,0} %q, bf16[16,32768,192]{2,1,0}"
         " %k, s8[64,4096,512]{2,1,0} %m)", 1.0, 0.050),
        ("%sparse_decode_index_scores.3 = f32[16,1,32768]{2,1,0} custom-call("
         "s32[1]{0} %a, s32[16]{0} %n)", 2.0, 0.0002),
        ("%fusion.9 = bf16[16,128,640]{2,1,0} fusion(bf16[5,16,32768,640] %c)",
         3.0, 0.001)]}
    calls = slr.reduce_calls(lines)
    assert calls == [["select_rows", 0.020, 4096, 32768],
                     ["masked_attention", 0.050, 16, 4096, 32768, 128],
                     ["decode_index_scores", 0.0002, 16, 32768]]
    ctx = _ctx()
    ctx["_sparse_latent_calls"] = calls
    ctx["capture"] = {"metrics_before": {slr.LIVE: [({}, 0.0)]},
                      "metrics_after": {slr.LIVE: [({}, 50000.0)]}}
    share = _reader("sparse_attention_roofline")(ctx)
    select = 2 * 64 * 128 * 4096 * 32769 / 2 / 197e12
    pairs = sum(min(2048, t + 1) for t in range(32768)) * 4096 / 32768
    # a group of 16 heads: the mask's byte a pair outweighs its operations
    attend = max(2 * 16 * pairs * 320 / 197e12,
                 (2 * 16 * 320 * (4096 + 32768) + 4096 * 32768) / 819e9)
    scores = (2 * (50000 * 128 + 16 * 64 * 128) + 4 * 50000) / 819e9
    assert share == pytest.approx(
        100 * (select + attend + scores) / 0.0702, rel=1e-6)


# --- a whole run of a tiny cell ----------------------------------------------

def _add_the_family(root):
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(FAMILY, kind), root / "bench" / kind,
                        dirs_exist_ok=True)
    harness._add_entries(root, configs=[{
        "name": "tiny-sparse-latent-moe", "source": "test",
        "file": "bench/configs/tiny-sparse-latent-moe.json", "reduced": [],
        "why": "test"}],
        workloads=[{"name": "sparse-latent.long", "config":
                    "tiny-sparse-latent-moe", "traffic": "tiny-long-context",
                    "chips": 1, "why": "test"}])


@pytest.mark.parametrize("altered", [False, True])
def test_the_family_is_launched_served_and_checked_past_its_selection(
        altered, tmp_path, monkeypatch, capsys):
    """``launchers/sparse_latent_moe.py`` and ``reference/sparse_latent_moe.py``
    under ``benchmark/`` serve a configuration beside the fixtures: prompts of
    12-100 tokens against a selection of 8 rows, through ServingCell and the
    engine's own programs; the sound run is correct, an altered token is
    not."""
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
    spec = run.load_cell(str(root), "sparse-latent.long")
    child = inproc.InProcessCell(spec, 23)
    try:
        out = run.drive(child, spec, 23, 3.0, False, str(tmp_path),
                        time.monotonic())
        held = child.engine._cache_shapes().held[0]
    finally:
        child.close()
    text = capsys.readouterr().out
    assert isinstance(child.engine.cfg, slm.SparseLatentMoEConfig)
    assert {k: v.shape for k, v in held.items()} == {
        "kidx": (3, 4, 128, 16), "ckv": (3, 4, 128, 128)}
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert "have a router near-tie" in text
    if altered:
        assert out["correct"] is False
        assert out["checks"]["reference"] is False, text
    else:
        assert inproc.sound(out), text
        assert out["compared"]["gap_max"]["value"] < 0.01
    harness._nothing_that_was_there_changed(before)
