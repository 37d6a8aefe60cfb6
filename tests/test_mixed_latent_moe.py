"""Two kinds of latent attention in one decoder (models/sparse_latent_moe.py
with ``layer_types``: selecting full layers beside window layers that hold a
ring of their OWN latent row; the ``dots3_note`` block), on the CPU at tiny
sizes with seeded random weights: the program's logits against the plain
reference's (``benchmark/reference/mixed_latent_moe.py``, which imports
nothing of the program), through the model and through the engine; the ring
of named arrays; the window decode kernel in interpret mode; the shares of an
expert-parallel deployment adding up; the bytes at the published widths."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import families, kv_kinds
from kukeon_tpu.models import sparse_latent_moe as slm
from kukeon_tpu.ops import sparse_attention as sa
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

SEED = 5
ROWS = 128
TOL = 2e-4          # float32 both sides; logits of deviation ~1


def reference_config(cfg: slm.SparseLatentMoEConfig) -> dict:
    """The keys ``benchmark/reference/mixed_latent_moe.py`` reads, for a
    program config (what ``benchmark/launchers/mixed_latent_moe.py`` maps the
    other way)."""
    s = cfg.sliding
    gate = "headwise" if cfg.head_gate else None
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.num_dense_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": None,
        "swa_num_attention_heads": s.num_heads,
        "swa_q_lora_rank": s.q_lora_rank, "swa_kv_lora_rank": s.kv_lora_rank,
        "swa_qk_nope_head_dim": s.qk_nope_head_dim,
        "swa_qk_rope_head_dim": s.qk_rope_head_dim,
        "swa_v_head_dim": s.v_head_dim, "swa_rope_theta": s.rope_theta,
        "sliding_window_size": s.window,
        "attention_gate_type": gate, "swa_attention_gate_type": gate,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "router_experts": cfg.num_experts,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.experts_per_token,
        "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "routed_scaling_factor": cfg.route_scale,
        "norm_topk_prob": cfg.route_norm,
        "torch_dtype": jnp.dtype(cfg.dtype).name}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(slm.mixed_latent_moe_tiny(), max_seq_len=ROWS)
    return cfg, slm.init_params(jax.random.key(SEED), cfg)


@pytest.fixture(scope="module")
def reference():
    return plugins.load("reference", "mixed_latent_moe")


@pytest.fixture(scope="module")
def tokens(tiny):
    return np.random.default_rng(0).integers(
        0, tiny[0].vocab_size, 100).astype(np.int32)


def _padded(seq, n, bucket):
    out = np.zeros((1, bucket), np.int32)
    out[0, :n] = seq[:n]
    return jnp.asarray(out)


def _empty_cache(cfg, kinds, slots):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        kv_kinds.shapes(kinds, slots, cfg.num_kv_heads, cfg.head_dim,
                        cfg.dtype))


# --- the model against the reference ------------------------------------------

@pytest.mark.parametrize("n, bucket", [(3, 16), (7, 16), (8, 16), (9, 16),
                                       (33, 64), (70, 128)])
def test_a_right_padded_prefill_gives_the_reference_logits_at_its_length(
        tiny, reference, tokens, n, bucket):
    """Prompts under, at and past the window of 7 and the selection of 8, in
    buckets that pad them: the padding takes no part in any real row."""
    cfg, params = tiny
    logits, block, counters = slm.prefill(params, cfg,
                                          _padded(tokens, n, bucket), n)
    want = reference.logits_at(reference_config(cfg), SEED, [tokens[:n]],
                               [np.array([n - 1])], ROWS)[0][0]
    assert np.abs(np.asarray(logits) - want).max() < TOL
    # two full layers' rows and keys, two window layers' own latent rows (64
    # values in 128 lanes), every row of the prompt: the ring is insert's
    assert {k: v.shape for k, v in block.items()} == {
        "ckv": (2, 1, bucket, 128), "kidx": (2, 1, bucket, 16),
        "wckv": (2, 1, bucket, 128)}
    assert slm.counters(cfg) == slm.COUNTERS + slm.WINDOW_COUNTERS
    (routed, hits, held, reached, pair_rows, worked, routed_tokens, selected,
     live, read, rows_held) = np.asarray(counters)
    # three expert layers of 4 held experts, one piece each
    assert held == 3 * 4 and 0 < reached <= held
    assert pair_rows == 3 * bucket * 4 and hits <= worked <= pair_rows
    assert routed_tokens == 3 * n and routed == 3 * n * 4
    assert 0 < hits < routed and selected == live == read == rows_held == 0


def test_prefill_then_decode_through_both_caches_matches_the_full_forward(
        tiny, reference, tokens):
    """40 tokens prefilled into slot 1 of two, then 58 decode steps through
    ``kv_kinds.insert`` / ``append``: the ring of 16 rows wraps more than
    three times and attends 6 of them, the full layers select 8 of up to 98
    rows; the absorbed form against the reference's expanded full forward at
    every step, the other slot idle."""
    cfg, params = tiny
    P = 40
    want = reference.logits_at(reference_config(cfg), SEED, [tokens[:99]],
                               [np.arange(P - 1, 98)], ROWS)[0]
    kinds = cfg.cache_kinds(ROWS)
    assert [(kd.name, kd.rows, kd.ring, kd.window, kd.layers) for kd in kinds
            ] == [("latent", ROWS, False, 0, (0, 1)),
                  ("window_latent", 16, True, 7, (2, 3))]
    logits, block, _ = slm.prefill(params, cfg, _padded(tokens, P, 64), P)
    assert np.abs(np.asarray(logits) - want[0]).max() < TOL
    cache = kv_kinds.insert(_empty_cache(cfg, kinds, 2), kinds, block, P, 1)
    active = jnp.array([False, True])
    step = jax.jit(lambda t, c: slm.decode(params, cfg, t, c, kinds, active))
    worst = 0.0
    for n in range(P, 98):
        lg, new, counters = step(jnp.array([0, tokens[n]], jnp.int32), cache)
        assert {k: v.shape for k, v in new.items()} == {
            "ckv": (2, 2, 1, 128), "kidx": (2, 2, 1, 16),
            "wckv": (2, 2, 1, 128)}
        cache = kv_kinds.append(cache, kinds, new, active)
        worst = max(worst, float(np.abs(np.asarray(lg[1])
                                        - want[n - P + 1]).max()))
        *_moe, routed_tokens, selected, live, read, held = np.asarray(counters)
        # one active slot: 8 of its n + 1 positions a full layer (live is
        # counted over the FULL layers only), 7 a window layer
        assert (routed_tokens, selected, live) == (3, 2 * 8, 2 * (n + 1))
        assert (read, held) == (2 * 7, 2 * (n + 1))
    assert worst < TOL
    assert cache.lengths.tolist() == [0, 98]


@pytest.mark.parametrize("n", [3, 6, 7, 20])
def test_the_absorbed_decode_is_the_expanded_prefill_one_token_on(
        tiny, tokens, n):
    """The two forms of one attention, both the program's, on prompts shorter
    than the window and the selection and past both: a decode step at
    position n against a prefill of n + 1 tokens at its last row."""
    cfg, params = tiny
    kinds = cfg.cache_kinds(ROWS)
    _, block, _ = slm.prefill(params, cfg, _padded(tokens, n, 64), n)
    cache = kv_kinds.insert(_empty_cache(cfg, kinds, 1), kinds, block, n, 0)
    lg, _, _ = slm.decode(params, cfg, jnp.asarray(tokens[n:n + 1]), cache,
                          kinds, jnp.array([True]))
    want, _, _ = slm.prefill(params, cfg, _padded(tokens, n + 1, 64), n + 1)
    assert float(jnp.abs(lg[0] - want).max()) < TOL


@pytest.mark.parametrize("switch, what", [
    ({"head_gate": False}, "the gate a head"),
    ({"lora_rescale": False}, "the rescale of the normed latents"),
    ({"sliding": dataclasses.replace(
        slm.mixed_latent_moe_tiny().sliding, window=9)}, "the window"),
    ({"sliding": dataclasses.replace(
        slm.mixed_latent_moe_tiny().sliding, rope_theta=1e4)},
     "the window layers' own rotary base")])
def test_each_reading_of_the_config_matters(tiny, reference, tokens, switch,
                                            what):
    """Switched off or changed in the program alone, each moves the logits
    past the tolerance the two sides otherwise agree in."""
    cfg, params = tiny
    other = dataclasses.replace(cfg, **switch)
    if "head_gate" in switch:       # a program without the gate has no leaf
        params = {**params, "layers": [
            {k: v for k, v in w.items() if k != "wg"}
            for w in params["layers"]]}
    n = 33
    logits, _, _ = slm.prefill(params, other, _padded(tokens, n, 64), n)
    want = reference.logits_at(reference_config(cfg), SEED, [tokens[:n]],
                               [np.array([n - 1])], ROWS)[0][0]
    assert np.abs(np.asarray(logits) - want).max() > 50 * TOL, what


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(
        tiny, reference, tokens):
    """What an expert-parallel deployment's combine adds up. The tiny router
    scores 16 experts and a chip holds 4: the PROGRAM's expert layer on each
    of the four shares (experts 0-3, 4-7, 8-11, 12-15, each drawing its own
    experts by their numbers) gives the shared expert plus its routed part;
    the four routed parts plus the shared expert counted ONCE, on the
    attention's output, are the residual the REFERENCE computes for the layer
    with all 16 held."""
    from kukeon_tpu.models.expert_layer import swiglu
    from kukeon_tpu.ops.norms import rms_norm

    cfg, _ = tiny
    n, S = 33, 64
    two = dataclasses.replace(cfg, num_layers=2, layer_types=(slm.FULL,) * 2)
    uncut = {**reference_config(two), "experts_held": [0, 16]}
    cfg_t = tuple(sorted(reference.dims(uncut).items()))
    root = jax.random.key(SEED)
    x = reference._embed(root, _padded(tokens, n, S)[0], cfg_t, "f32")
    bias = reference._fitted_bias(root, jnp.int32(1), cfg_t)
    x0, _, _ = reference._layer(root, jnp.int32(0), x, jnp.zeros_like(bias),
                                cfg_t, "f32", True, slm.FULL)
    want, _, _ = reference._layer(root, jnp.int32(1), x0, bias, cfg_t, "f32",
                                  False, slm.FULL)
    routed, once = 0.0, None
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(two, experts_held=(first, 4))
        w = slm.init_params(jax.random.key(SEED), share)["layers"][1]
        attended, _ = slm._prefill_attention(x0, w, share, share.attention(1))
        out, tally = slm._mlp(attended, w, share, jnp.arange(S) < n)
        shared = swiglu(rms_norm(attended, w["norm2"], share.rms_norm_eps),
                        w["s_gate"], w["s_up"], w["s_down"])
        part = out - attended - shared
        assert float(jnp.abs(part[:n]).max()) > 1e-3 and int(tally[0]) > 0
        routed = routed + part
        once = attended + shared      # the same on every chip
    assert float(jnp.abs((once + routed)[:n] - want[:n]).max()) < 1e-5


# --- the engine ---------------------------------------------------------------

def test_the_engine_serves_two_slots_through_both_caches(tiny, reference):
    """ServingEngine's own prefill, insert and decode_chunk: two requests of
    12 and 40 tokens decode side by side, then a third takes the freed slot;
    every served token is the reference's best at its position, the state
    holds both kinds' named arrays, and the counters say what a step read."""
    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=ROWS,
                        decode_chunk=4, prefill_buckets=(16, 32, 64, 128))
    assert eng.family.name == "sparse_latent_moe"
    latent, ring = eng.state.cache.held
    assert {k: v.shape for k, v in latent.items()} == {
        "kidx": (2, 2, 128, 16), "ckv": (2, 2, 128, 128)}
    assert {k: v.shape for k, v in ring.items()} == {"wckv": (2, 2, 16, 128)}
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (12, 40, 5)]
    news = (30, 21, 25)
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=m))
            for p, m in zip(prompts, news)]
    rows = {}
    while not all(r.done.is_set() for r in reqs):
        eng.step()
        rows = {s[0]["kind"]: s[1] for fam in eng._obs_collect()
                if fam[0] == "kukeon_engine_kv_rows" for s in fam[3]} \
            if eng._active_requests() else rows
    # the gauge reads both kinds: a ring holds at most its 16 rows a slot
    assert set(rows) == {"latent", "window_latent"}
    assert 0 < rows["window_latent"] <= 2 * 16 and rows["latent"] > 0
    for prompt, req in zip(prompts, reqs):
        seq = np.concatenate([prompt, req.generated])
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = reference.logits_at(reference_config(cfg), SEED, [seq],
                                     [pos], ROWS)[0]
        gaps = logits.max(-1) - logits[np.arange(len(pos)), seq[pos + 1]]
        assert gaps.max() < 1e-4
    value = lambda name, **kw: eng.registry.get(name).value(**kw)  # noqa: E731
    read = value("kukeon_window_latent_rows_read_total")
    held = value("kukeon_window_latent_rows_held_total")
    live = value("kukeon_sparse_rows_live_total")
    # two window layers and two full ones: the same tokens held under both
    assert 0 < read < held == live
    assert value("kukeon_sparse_rows_selected_total") < live
    # what the engine counts of a step's rows reads both kinds of arrays: a
    # step holds 2 slots x (2 x 128 + 2 x 16) rows and fetches the live ones
    held_rows = value("kukeon_engine_decode_kv_rows_total", what="held")
    read_rows = value("kukeon_engine_decode_kv_rows_total", what="read")
    assert 0 < read_rows < held_rows and held_rows % (2 * 2 * (128 + 16)) == 0
    assert eng._rows_by_kind([40, 5]) == {
        "latent_rows": 45, "window_latent_rows": 16 + 5}
    assert eng._decode_kv_rows([40, 5]) == (
        2 * 2 * (128 + 16), 2 * 45 + 2 * (16 + 5))


@pytest.mark.parametrize("kwargs,what", [
    ({"dtype": "int8"}, "--dtype int8"),
    ({"kv_cache_int8": True}, "--kv-cache-int8"),
    ({"kv_page_tokens": 16}, "--kv-page-tokens"),
    ({"chips": 2}, "--chips > 1"),
    ({"checkpoint": "/nonexistent"}, "--checkpoint"),
])
def test_what_the_family_lacks_ends_the_boot(kwargs, what):
    from kukeon_tpu.runtime.serving_cell import ServingCell

    args = {"num_slots": 2, "max_seq_len": 64, "checkpoint": None,
            "dtype": None, "chips": 1, **kwargs}
    with pytest.raises(SystemExit, match=what):
        ServingCell("mixed-latent-moe-tiny", **args)


def test_the_cell_boots_and_answers_at_the_tiny_preset():
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("mixed-latent-moe-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None, chips=1)
    assert cell.engine.family is families.of(cell.cfg)
    assert cell.engine.family.name == "sparse_latent_moe"
    out = cell.generate({"prompt": "hello there", "maxNewTokens": 40})
    assert out["numTokens"] == 40
    from kukeon_tpu.obs import expo

    text = expo.render(cell.engine.registry)
    for name in slm.WINDOW_COUNTERS + ('kukeon_engine_kv_rows{kind="window_latent"}',):
        assert name in text, name


def test_the_one_family_still_states_the_config_every_layer_alike():
    """``deepseek_v32_exp()`` is the case "every layer full, no gate, no
    rescale, YaRN, 8 groups" of the same functions: one kind of cache, the
    seven counters it had, no gate leaf, and ``full`` the numbers its flat
    fields state."""
    cfg = slm.deepseek_v32_exp()
    assert cfg.layers_of(slm.FULL) == tuple(range(61))
    assert not cfg.layers_of(slm.SLIDING) and slm.counters(cfg) == slm.COUNTERS
    kind, = cfg.cache_kinds(32768)
    assert (kind.name, kind.ring, kind.window) == ("latent", False, 0)
    assert cfg.full.yarn == (40.0, 4096, 32.0, 1.0, 1.0)
    assert "wg" not in slm._layer_leaves(cfg, False)
    assert "wi_q" in slm._layer_leaves(cfg, False)
    dots = slm.dots3_note_prev()
    assert dots.full.yarn is None and dots.sliding.window == 513
    leaves = slm._layer_leaves(dots, False, dots.sliding)
    assert "wi_q" not in leaves and leaves["wg"][1] == (5120, 64)
    assert len(dots.layers_of(slm.FULL)) == 13
    assert len(dots.layers_of(slm.SLIDING)) == 33
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(dots, num_layers=5)
    with pytest.raises(ValueError, match="sliding"):
        dataclasses.replace(dots, sliding=None)


# --- the window decode kernel -------------------------------------------------

def _window_inputs(B, NH, W, layers, rows, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (B, NH, W), jnp.float32),
            jax.random.normal(ks[1], (B, W), jnp.float32),
            jax.random.normal(ks[2], (layers, B, rows, W), jnp.float32))


@pytest.mark.parametrize("rows, window, block", [
    (16, 17, 8),        # the ring holds exactly the positions behind
    (16, 7, 8),         # ... more than the window attends
    (32, 20, 16), (32, 33, 32)])
def test_the_window_kernel_is_the_softmax_of_a_loop_written_out(
        rows, window, block):
    """``window_decode_attention``, the XLA body and the kernel in interpret
    mode, against a softmax written out position by position over what a ring
    holds: slots before the ring fills, at the wrap, wrapped many times, and
    an idle one (length 0: its own row alone)."""
    B, NH, W, R = 5, 4, 128, 128
    q, new, stack = _window_inputs(B, NH, W, 3, rows)
    lengths = jnp.array([0, 3, rows, rows + 1, 5 * rows + 3], jnp.int32)
    layer = jnp.int32(1)
    want = np.zeros((B, NH, R), np.float32)
    for b, t in enumerate(np.asarray(lengths)):
        # position p lives in row p % rows; the step is position t
        seen = [p for p in range(max(0, t - (window - 1)), t)]
        keys = np.stack([np.asarray(stack[1, b, p % rows]) for p in seen]
                        + [np.asarray(new[b])])
        s = np.einsum("hw,kw->hk", np.asarray(q[b]), keys) * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want[b] = (p / p.sum(-1, keepdims=True)) @ keys[:, :R]
    got = sa.window_decode_attention(q, new, stack, layer, lengths,
                                     window=window, scale=0.1, value_dim=R)
    np.testing.assert_allclose(got, want, atol=2e-5)
    kernel = sa.window_decode_attention(
        q, new, stack, layer, lengths, window=window, scale=0.1, value_dim=R,
        block=block, interpret=True)
    np.testing.assert_allclose(kernel, want, atol=2e-5)


def test_a_ring_too_short_for_its_window_is_refused():
    q, new, stack = _window_inputs(1, 2, 128, 1, 16)
    with pytest.raises(ValueError, match="ring of 16 rows"):
        sa.window_decode_attention(q, new, stack, jnp.int32(0),
                                   jnp.array([4]), window=18, scale=1.0,
                                   value_dim=128)


@pytest.mark.parametrize("rows, block", [(32768, 2048), (20480, 1280),
                                         (4096, 512), (1024, 128)])
def test_the_selecting_kernel_takes_the_largest_block_that_divides_a_run(
        rows, block, monkeypatch):
    """A slot's scores lie in 8 runs of rows / 8; a block is whole lanes of
    rows and divides a run: 20480 rows (runs of 2560) take 1280, where
    ``min(2048, run)`` divides nothing and the kernel stood aside."""
    seen = {}

    def call(kernel, **kw):
        seen["rows"] = kw["scratch_shapes"][0].shape[1]
        raise RuntimeError("seen")

    monkeypatch.setattr(sa, "decode_kernel_runs", lambda rows, w: True)
    monkeypatch.setattr(sa.pl, "pallas_call", call)
    B, NH, W = 2, 4, 128
    with pytest.raises(RuntimeError, match="seen"):
        sa.decode_attention.__wrapped__(
            jnp.zeros((B, NH, W)), jnp.zeros((B, W)), jnp.zeros((B,)),
            jnp.zeros((B, rows)), jnp.zeros((1, B, rows, W)), jnp.int32(0),
            jnp.zeros((B,), jnp.int32), topk=8, scale=1.0, value_dim=128)
    assert seen["rows"] == block


# --- the published widths -----------------------------------------------------

def test_the_served_share_holds_8_18_gb_of_weights_and_2_13_gb_of_cache():
    """At the benchmark's cut (shapes only, nothing is allocated), through the
    cell's launcher: 32 slots; two full layers of 20480 rows, a row the 576
    latent values in 640 lanes and 128 of the index key; three rings of 512
    rows (the 512 positions behind a window of 513; the step's own row takes
    part unwritten) of 1088 values in 1152 lanes. The configuration file's
    ``deployment`` states these numbers."""
    with open(os.path.join(plugins.HERE, "configs",
                           "dots3-note-prev-ep8-bf16.json")) as f:
        config = json.load(f)
    family = plugins.load("launchers", "mixed_latent_moe").abstract(config)
    cfg, params = family["cfg"], family["params"]
    assert cfg == dataclasses.replace(
        slm.dots3_note_prev(), num_layers=5,
        layer_types=slm.dots3_note_prev().layer_types[:5],
        experts_held=(0, 32), vocab_size=19008, max_seq_len=20480)
    latent, ring = cfg.cache_kinds(20480)
    assert (latent.name, latent.rows, latent.select, latent.layers) == (
        "latent", 20480, 2048, (0, 1))
    assert (ring.name, ring.rows, ring.ring, ring.window, ring.layers) == (
        "window_latent", 512, True, 513, (2, 3, 4))
    shapes = kv_kinds.shapes((latent, ring), 32, cfg.num_kv_heads,
                             cfg.head_dim, cfg.dtype)
    assert {k: v.shape for h in shapes.held for k, v in h.items()} == {
        "kidx": (2, 32, 20480, 128), "ckv": (2, 32, 20480, 640),
        "wckv": (3, 32, 512, 1152)}
    held = sum(np.prod(s.shape) * 2 for h in shapes.held for s in h.values())
    assert held == 32 * 2 * (2 * 20480 * (640 + 128) + 3 * 512 * 1152)
    assert 2.12e9 < held < 2.13e9

    def size(tree):
        return sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    full = 5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 \
        + 128 * 128 * 5120 + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64 \
        + 5120 * 128
    sliding = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 \
        + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64
    expert = 3 * 5120 * 1536
    assert full / 1e6 == pytest.approx(144.0, abs=0.5)
    assert sliding / 1e6 == pytest.approx(90.8, abs=0.5)
    layers = [size(w) for w in params["layers"]]
    assert layers[0] / 1e9 == pytest.approx(0.713, abs=0.003)
    assert layers[1] / 1e9 == pytest.approx(1.8505, abs=0.001)
    assert layers[2] / 1e9 == pytest.approx(1.7441, abs=0.001)
    assert layers[2] == layers[3] == layers[4]
    # bf16 but the router and its bias; the norms' gains are the rest
    assert layers[1] == pytest.approx(
        2 * (full + 33 * expert) + 4 * 5120 * 256, rel=1e-4)
    assert 8.17e9 < size(params) < 8.20e9
    assert (cfg.full.softmax_scale, cfg.sliding.softmax_scale) == (
        192 ** -0.5, 256 ** -0.5)
    assert json.dumps(config["deployment"]).count("8.18 GB") == 1
    assert "2.13 GB" in config["deployment"]
