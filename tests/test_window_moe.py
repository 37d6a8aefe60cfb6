"""The window_moe family (models/window_moe.py, models/expert_layer.py,
models/kv_kinds.py) on the CPU at its tiny preset: a window of 8 rows, contexts
of 40, against the benchmark's plain reference (logits, not tokens)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import expert_layer as el
from kukeon_tpu.models import families, kv_kinds
from kukeon_tpu.models import window_moe as wm
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

SEED = 7


def reference_config(cfg: wm.WindowMoEConfig) -> dict:
    """The keys ``benchmark/reference/window_moe.py`` reads, for a program
    config (what ``benchmark/launchers/window_moe.py`` maps the other way)."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "layer_types": list(cfg.layer_types),
        "num_hidden_layers": cfg.num_layers,
        "num_dense_layers": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "router_experts": cfg.num_experts,
        "num_experts": cfg.experts_held[1],
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.experts_per_token,
        "sliding_window": cfg.sliding_window, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps, "route_scale": cfg.route_scale,
        "route_norm": cfg.route_norm,
        "torch_dtype": jnp.dtype(cfg.dtype).name}


@pytest.fixture(scope="module")
def tiny():
    cfg = wm.window_moe_tiny()
    return cfg, wm.init_params(jax.random.key(SEED), cfg)


@pytest.fixture(scope="module")
def reference():
    return plugins.load("reference", "window_moe")


def _expert_weights(E=16, H=32, I=24, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    n = jax.random.normal
    return {"router": n(ks[0], (H, E)), "bias": 0.05 * n(ks[1], (E,)),
            "e_gate": n(ks[2], (E, H, I)) * H ** -.5,
            "e_up": n(ks[3], (E, H, I)) * H ** -.5,
            "e_down": n(ks[4], (E, I, H)) * I ** -.5,
            "s_gate": n(ks[5], (H, I)) * H ** -.5,
            "s_up": n(ks[6], (H, I)) * H ** -.5,
            "s_down": n(ks[7], (I, H)) * I ** -.5}


def _held(w, first, count):
    return {**w, **{k: w[k][first:first + count]
                    for k in ("e_gate", "e_up", "e_down")}}


def test_prefill_then_decode_through_the_ring_matches_the_full_forward(
        tiny, reference):
    """A prompt of 19 tokens in a bucket of 32 (past the window of 8: the
    ring takes its last 8 rows), then 21 decode steps to position 40, slot 1
    of 2: every step's logits against the reference's cacheless forward."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    n = 19
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(n - 1, 40)], 64)[0]
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = seq[:n]
    last, block, counted = jax.jit(
        lambda p, t, m: wm.prefill(p, cfg, t, m))(params, tokens, n)
    assert np.abs(np.asarray(last) - want[0]).max() < 2e-4
    assert sorted(block) == ["k", "v"] == list(kv_kinds.names(
        cfg.cache_kinds(64)))
    assert block["k"].shape == (cfg.num_layers, 1, 32, cfg.num_kv_heads,
                                cfg.head_dim)
    # 19 real tokens x 8 expert layers x top-4; padding is not counted
    assert int(counted[0]) == n * 8 * 4 and 0 < int(counted[1]) < n * 8 * 4

    kinds = cfg.cache_kinds(64)
    assert [(k.name, k.rows, k.ring, len(k.layers)) for k in kinds] == [
        ("window", 8, True, 7), ("full", 64, False, 2)]
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        kv_kinds.shapes(kinds, 2, cfg.num_kv_heads, cfg.head_dim, cfg.dtype))
    cache = kv_kinds.insert(cache, kinds, block, n, 1)
    active = jnp.array([False, True])

    @jax.jit
    def step(cache, token):
        view = kv_kinds.view(cache)
        logits, new, counted = wm.decode(params, cfg, token, view, kinds,
                                         active)
        return logits, kv_kinds.view(
            kv_kinds.append(view, kinds, new, active)), counted

    for i in range(n, 40):
        logits, cache, counted = step(cache, jnp.array([0, seq[i]], jnp.int32))
        assert np.abs(np.asarray(logits[1]) - want[i - n + 1]).max() < 2e-4, i
    assert np.asarray(cache.lengths).tolist() == [0, 40]
    assert int(counted[0]) == 1 * 8 * 4       # the one active slot


def test_the_engine_serves_two_slots_of_different_lengths_through_the_ring(
        tiny, reference):
    """ServingEngine's own prefill, insert and decode_chunk: two requests of
    5 and 19 tokens decode side by side past the window; every served token
    is the reference's best at its position (a gap of zero in its logits).
    The state holds a window layer's 8 rows beside a full layer's 64."""
    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=64,
                        decode_chunk=4, prefill_buckets=(16, 32, 64))
    assert [x.shape for x in eng.state.cache.k] == [
        (7, 2, 2, 8, 16), (2, 2, 2, 64, 16)]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 5),
               rng.integers(0, cfg.vocab_size, 19)]
    fetches0 = eng.sync_stats["fetches"]
    reqs = [eng.submit(prompts[0], SamplingParams(max_new_tokens=30)),
            eng.submit(prompts[1], SamplingParams(max_new_tokens=21))]
    rows = {}
    while not all(r.done.is_set() for r in reqs):
        eng.step()
        rows = {s[0]["kind"]: s[1] for fam in eng._obs_collect()
                if fam[0] == "kukeon_engine_kv_rows" for s in fam[3]} \
            if eng._active_requests() else rows
    assert rows["window"] <= 2 * 8 < rows["full"] <= 45 + 40
    # one blocking fetch a chunk and one for the two first tokens
    assert (eng.sync_stats["fetches"] - fetches0
            <= eng.sync_stats["chunks"] + 2)
    for prompt, req in zip(prompts, reqs):
        seq = np.concatenate([prompt, req.generated])
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = reference.logits_at(reference_config(cfg), SEED, [seq],
                                     [pos], 64)[0]
        gaps = logits.max(-1) - logits[np.arange(len(pos)), seq[pos + 1]]
        assert gaps.max() < 1e-4
    routed = eng.registry.get("kukeon_moe_routed_total").value()
    hits = eng.registry.get("kukeon_moe_held_hits_total").value()
    assert routed >= (5 + 19 + 29 + 20) * 8 * 4 and 0 < hits < routed


def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """What an expert-parallel combine adds up: the routed parts of the
    shares (each chip's held experts) plus the shared expert, once, equal the
    layer that holds every expert; the shares' hits are the choices made."""
    w = _expert_weights()
    h = jax.random.normal(jax.random.key(9), (50, 32))
    kw = dict(experts_per_token=4, route_scale=2.448,
              counted=jnp.ones(50, bool))
    whole, counts = el.expert_layer_counts(h, w, experts_held=(0, 16), **kw)
    hits, held, reached = counts[:len(el.TALLY)]
    assert held == 16 and 0 < reached <= 16
    shared = el.swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    parts, counted = shared, 0
    for share in range(8):
        y, n = el.expert_layer_counts(h, _held(w, 2 * share, 2),
                                      experts_held=(2 * share, 2), **kw)
        parts = parts + (y - shared)
        counted += int(n[0])
    assert jnp.abs(parts - whole).max() < 1e-5
    assert counted == int(hits) == 50 * 4


def test_the_bias_changes_selections_and_never_the_weights():
    w = _expert_weights()
    h = jax.random.normal(jax.random.key(3), (200, 32))
    sel, wts = el.route(h, w["router"], w["bias"] * 4, 4, scale=2.448)
    sel0, wts0 = el.route(h, w["router"], jnp.zeros(16), 4, scale=2.448)
    assert (np.sort(sel, -1) != np.sort(sel0, -1)).any()
    s = jax.nn.sigmoid(h @ w["router"])
    picked = jnp.take_along_axis(s, sel, -1)
    want = picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.448
    np.testing.assert_allclose(wts, want, rtol=1e-5)
    # route_norm off: the raw scores, scaled
    _, raw = el.route(h, w["router"], w["bias"] * 4, 4, norm=False, scale=3.0)
    np.testing.assert_allclose(raw, picked * 3.0, rtol=1e-5)
    np.testing.assert_allclose(wts.sum(-1), 2.448, rtol=1e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """A routing so skewed that a capacity would overflow: all 64 tokens
    choose experts 0-3, which this chip holds. Each token's output is still
    its own weighted sum of the four."""
    w = _expert_weights()
    w["router"] = jnp.zeros_like(w["router"])
    w["bias"] = jnp.where(jnp.arange(16) < 4, 1.0, 0.0)
    h = jax.random.normal(jax.random.key(5), (64, 32))
    y, counts = el.expert_layer_counts(
        h, _held(w, 0, 8), experts_per_token=4, experts_held=(0, 8),
        route_scale=1.0, counted=jnp.ones(64, bool))
    hits, held, reached = counts[:len(el.TALLY)]
    want = el.swiglu(h, w["s_gate"], w["s_up"], w["s_down"]) + sum(
        0.25 * el.swiglu(h, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        for e in range(4))
    assert (int(hits), int(held), int(reached)) == (64 * 4, 8, 4)
    assert jnp.abs(y - want).max() < 1e-5


def test_full_layers_ignore_positions_and_window_layers_do_not(tiny):
    """No positional encoding on a full layer: its q and k are the same
    wherever the tokens stand; a window layer rotates them."""
    cfg, params = tiny
    x = jax.random.normal(jax.random.key(1), (1, 6, cfg.hidden_size))
    w = jax.tree.map(lambda a: a[0], params["period"][0])
    at0 = jnp.arange(6)[None]
    for rotary, same in ((False, True), (True, False)):
        q0, k0, *_ = wm._qkvg(x, w, cfg, at0, rotary)
        q9, k9, *_ = wm._qkvg(x, w, cfg, at0 + 9, rotary)
        assert bool(jnp.allclose(q0, q9) and jnp.allclose(k0, k9)) is same
    # and in the whole model: the layer types decide which layers rotate
    q = jax.random.normal(jax.random.key(2), (1, 16, 4, 16))
    k = jax.random.normal(jax.random.key(3), (1, 16, 2, 16))
    full = wm.blocked_attention(q, k, k, None, 8)
    band = wm.blocked_attention(q, k, k, 8, 8)
    assert jnp.allclose(full[:, :8], band[:, :8])       # inside the window
    assert not jnp.allclose(full[:, 8:], band[:, 8:])   # past it


@pytest.mark.parametrize("window", [None, 8])
def test_query_blocks_give_what_one_block_gives(window):
    """Four blocks of 8 query rows, the later ones of a window layer slicing
    their band of keys, against all 32 rows at once under the mask alone
    (what a bucket under ``PREFILL_BLOCK`` rows runs)."""
    q = jax.random.normal(jax.random.key(2), (1, 32, 4, 16))
    k = jax.random.normal(jax.random.key(3), (1, 32, 2, 16))
    v = jax.random.normal(jax.random.key(4), (1, 32, 2, 16))
    np.testing.assert_allclose(wm.blocked_attention(q, k, v, window, 8),
                               wm.blocked_attention(q, k, v, window, 32),
                               atol=1e-5)


def test_the_served_state_holds_a_windows_rows_beside_full_ones():
    """At the benchmark's cut: four window layers hold 4096 rows a slot and
    the full layer 8192 (shapes only, nothing is allocated)."""
    published = wm.trinity_large_preview()      # layers 6, 7: a part period
    assert (published.num_unrolled, published.num_periods) == (8, 13)
    assert published.period == (wm.SLIDING,) * 3 + (wm.FULL,)
    cfg = dataclasses.replace(
        published, num_dense_layers=1,
        layer_types=(wm.SLIDING,) * 4 + (wm.FULL,), experts_held=(0, 32),
        vocab_size=25024, max_seq_len=8192)
    kinds = cfg.cache_kinds(8192)
    shapes = kv_kinds.shapes(kinds, 32, cfg.num_kv_heads, cfg.head_dim,
                             cfg.dtype)
    assert [s.shape for s in shapes.k] == [(4, 32, 8, 4096, 128),
                                           (1, 32, 8, 8192, 128)]
    held = sum(np.prod(s.shape) * 2 for s in shapes.k + shapes.v)
    assert held == 32 * (4 * 4096 + 8192) * 4096        # 3.22 GB
    params = jax.eval_shape(lambda k: wm.init_params(k, cfg),
                            jax.random.key(0))
    weights = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 8.6e9 < weights < 8.7e9


def test_a_prefix_id_is_a_counted_miss_and_nothing_is_stored(tiny):
    cfg, params = tiny
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=64,
                        prefill_buckets=(16, 64))
    prompt = np.arange(1, 12)
    for _ in range(2):
        req = eng.submit(prompt, SamplingParams(max_new_tokens=2),
                         prefix_id="session-1")
        while not req.done.is_set():
            eng.step()
    assert (eng.prefix_hits, eng.prefix_misses) == (0, 2)
    assert not eng._prefix_cache and eng._prefix_cache_size == 0
    with pytest.raises(ValueError, match="KV handoff"):
        eng.submit(prompt, export=True)


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
        ServingCell("window-moe-tiny", **args)


def test_the_cell_boots_and_answers_at_the_tiny_preset():
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("window-moe-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None, chips=1)
    assert cell.engine.family is families.of(cell.cfg)
    assert cell.engine.family.name == "window_moe"
    out = cell.generate({"prompt": "hello there", "maxNewTokens": 12})
    assert out["numTokens"] == 12
