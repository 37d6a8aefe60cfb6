"""The ssm_moe family (models/ssm_moe.py, ops/ssd_scan.py, the [1, I] decay of
ops/selective_scan.py's decode step, ``route``'s softmax over the selected
logits) on the CPU at its tiny preset, against the benchmark's plain reference
(logits, not tokens).

Tolerances. The preset is float32 on both sides, so program and reference
differ by the order of their sums alone (the chunked matrix form against the
recurrence a step at a time, the ragged products against an expert at a time):
2e-4 on logits of deviation ~1 (measured 1e-6 to 2e-5). The tests of a LOST or
ROUNDED state and of a ROUNDED router need the other direction: what they
plant has to move the logits by far more than that tolerance, or the
comparison would be blind to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import expert_layer as el
from kukeon_tpu.models import families, kv_kinds
from kukeon_tpu.models import ssm_moe as sm
from kukeon_tpu.ops import selective_scan as ss
from kukeon_tpu.ops import ssd_scan as sd
from kukeon_tpu.ops.norms import rms_norm
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

SEED = 7
TOL = 2e-4


def reference_config(cfg: sm.SsmMoEConfig) -> dict:
    """The keys ``benchmark/reference/ssm_moe.py`` reads, for a program
    config (what ``benchmark/launchers/ssm_moe.py`` maps the other way)."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.moe_intermediate_size,
        "shared_intermediate_size": cfg.shared_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.d_state, "mamba_d_conv": cfg.d_conv,
        "mamba_n_groups": 1, "mamba_proj_bias": False,
        "router_experts": cfg.num_experts,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.experts_per_token,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "rms_norm_eps": cfg.rms_norm_eps, "tie_word_embeddings": True,
        "torch_dtype": jnp.dtype(cfg.dtype).name}


@pytest.fixture(scope="module")
def tiny():
    cfg = sm.ssm_moe_tiny()
    return cfg, sm.init_params(jax.random.key(SEED), cfg)


@pytest.fixture(scope="module")
def reference():
    return plugins.load("reference", "ssm_moe")


@pytest.fixture(scope="module")
def prefill(tiny):
    cfg, _ = tiny
    return jax.jit(lambda p, t, n: sm.prefill(p, cfg, t, n))


def _padded(seq, n, bucket):
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n] = seq[:n]
    return tokens


def _empty_cache(cfg, kinds, slots):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        kv_kinds.shapes(kinds, slots, cfg.num_kv_heads, cfg.head_dim,
                        cfg.dtype))


def _engine(cfg, params, **kw):
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    return ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=128,
                         decode_chunk=4, prefill_buckets=(16, 32, 64, 128),
                         **kw)


# --- prefill alone, right-padded, at every length ------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 4, 19, 32])
def test_a_right_padded_prefill_gives_the_logits_and_the_state_at_its_length(
        tiny, reference, prefill, n):
    """A prompt of n tokens in a bucket of 32 (n = 1, 2, 3: shorter than the
    convolution; 19: inside the third chunk of 8): the logits are the
    reference's at position n - 1, and what the prefill leaves behind is what
    a prefill of the same prompt in its own smallest bucket leaves: a state
    that ran over the padding would differ."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 32)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(32)], 64)[0]
    last, block, counted = prefill(params, _padded(seq, n, 32), n)
    assert np.abs(np.asarray(last) - want[n - 1]).max() < TOL
    # every real token makes top-2 choices in each of the 8 layers
    routed, hits, held, reached, pair_rows, worked, pairs = (
        int(v) for v in counted)
    assert (routed, pairs) == (n * 8 * 2, n * 8) and 0 <= hits <= routed
    # the bucket's 32 rows make top-2 pairs in each layer: one block a layer
    assert pair_rows == 8 * 32 * 2 and hits <= worked <= pair_rows
    # 8 layers of 4 held experts; n tokens reach at most n of a layer's
    assert held == 8 * 4 and reached <= min(held, hits)
    M, I, N = cfg.num_mixers, cfg.d_inner, cfg.d_state
    assert {k: v.shape for k, v in block.items()} == {
        "k": (2, 1, 32, 2, 16), "v": (2, 1, 32, 2, 16),
        "conv": (M, 3, 1, I + 2 * N), "ssm": (M, 1, N, I)}
    assert block["ssm"].dtype == jnp.float32
    bucket = max(8, -(-n // 8) * 8)
    if bucket != 32:
        _, exact, _ = jax.jit(lambda p, t, m: sm.prefill(p, cfg, t, m))(
            params, _padded(seq, n, bucket), n)
        for name in ("conv", "ssm"):
            np.testing.assert_allclose(block[name], exact[name], atol=1e-5)
    # shorter than the convolution: the tail's oldest columns are zero
    assert (np.asarray(block["conv"][:, :max(0, 3 - n)]) == 0).all()
    assert np.isfinite(np.asarray(block["ssm"])).all()


def test_logits_at_every_position_of_a_prompt(tiny, reference, prefill):
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 16)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(16)], 64)[0]
    assert 0.8 < want.std() < 1.3      # the recipe: deviation ~1 after / 16
    for n in range(1, 17):
        last, _, _ = prefill(params, _padded(seq, n, 16), n)
        assert np.abs(np.asarray(last) - want[n - 1]).max() < TOL, n


# --- prefill, the engine's insert, then decode ---------------------------------

def _with_ssm(cache, fn):
    held = list(cache.held)
    held[0] = {**held[0], "ssm": fn(held[0]["ssm"])}
    return kv_kinds.LayeredKV(held=tuple(held), lengths=cache.lengths)


def _decode_after_prefill(cfg, params, prefill, seq, n, steps, spoil=None,
                          each_step=lambda ssm: ssm, rows=128):
    """Logits [steps, V] of slot 1 of 2: a prompt of n tokens right-padded to
    32, ``kv_kinds.insert``, then ``steps`` decode steps on the sequence's
    own tokens. ``spoil`` alters the scan state between the two,
    ``each_step`` after every step."""
    kinds = cfg.cache_kinds(rows)
    _, block, _ = prefill(params, _padded(seq, n, 32), n)
    cache = kv_kinds.insert(_empty_cache(cfg, kinds, 2), kinds, block, n, 1)
    if spoil is not None:
        cache = _with_ssm(cache, spoil)
    active = jnp.array([False, True])

    @jax.jit
    def step(cache, token):
        view = kv_kinds.view(cache)
        logits, new, _ = sm.decode(params, cfg, token, view, kinds, active)
        return logits, _with_ssm(kv_kinds.view(
            kv_kinds.append(view, kinds, new, active)), each_step)

    out = []
    for i in range(n, n + steps):
        logits, cache = step(cache, jnp.array([0, seq[i]], jnp.int32))
        out.append(np.asarray(logits[1]))
    return np.stack(out), cache


def test_prefill_in_a_larger_bucket_then_40_decode_steps_match_the_full_forward(
        tiny, reference, prefill):
    cfg, params = tiny
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 60)
    n = 19
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(n, n + 40)], 64)[0]
    got, cache = _decode_after_prefill(cfg, params, prefill, seq, n, 40)
    assert np.abs(got - want).max() < TOL
    assert np.asarray(cache.lengths).tolist() == [0, n + 40]
    # the slot that is not active kept its (empty) state, bit for bit
    state = cache.held[0]
    assert (np.asarray(state["ssm"][:, 0]) == 0).all()
    assert (np.asarray(state["conv"][:, :, 0]) == 0).all()
    assert np.abs(np.asarray(state["ssm"][:, 1])).max() > 0


def test_an_idle_slots_state_is_bit_for_bit_unchanged_by_a_step(tiny, prefill):
    """Slot 0 holds a request's state and is NOT active in the step; slot 1
    decodes. Every array slot 0 holds is what it was, to the bit."""
    cfg, params = tiny
    seq = np.random.default_rng(3).integers(0, cfg.vocab_size, 40)
    kinds = cfg.cache_kinds(128)
    cache = _empty_cache(cfg, kinds, 2)
    for slot, n in ((0, 11), (1, 19)):
        _, block, _ = prefill(params, _padded(seq, n, 32), n)
        cache = kv_kinds.insert(cache, kinds, block, n, slot)
    active = jnp.array([False, True])
    view = kv_kinds.view(cache)
    _, new, counted = jax.jit(
        lambda v, t: sm.decode(params, cfg, t, v, kinds, active))(
            view, jnp.array([5, 6], jnp.int32))
    after = kv_kinds.view(kv_kinds.append(view, kinds, new, active))
    before, now = cache.held[0], after.held[0]
    assert np.array_equal(before["ssm"][:, 0], now["ssm"][:, 0])
    assert np.array_equal(before["conv"][:, :, 0], now["conv"][:, :, 0])
    assert not np.array_equal(before["ssm"][:, 1], now["ssm"][:, 1])
    assert np.asarray(after.lengths).tolist() == [11, 20]
    # one active slot: top-2 in each of 8 layers, which hold 4 experts each
    # and read at most the two it chose
    routed, hits, held, reached, pair_rows, worked, pairs = (
        int(v) for v in counted)
    assert (routed, held, pairs) == (16, 32, 8) and reached <= hits <= 16
    # two slots' top-2 pairs a layer are one block, walked where one is held
    assert pair_rows == 8 * 4 and worked in range(0, pair_rows + 1, 4)


def test_the_check_sees_a_lost_state(tiny, reference, prefill):
    """The weights' recipe leaves the state a long memory: with the scan state
    zeroed between prefill and decode the logits of the next steps leave the
    reference by more than a hundred times the tolerance (measured 220: a
    mixer's branch enters the residual times 0.22, beside an expert layer's,
    so a state moves the logits less than in ``ssm_hybrid``)."""
    cfg, params = tiny
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 60)
    n = 19
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(n, n + 8)], 64)[0]
    got, _ = _decode_after_prefill(cfg, params, prefill, seq, n, 8,
                                   spoil=jnp.zeros_like)
    assert np.abs(got - want).max() > 100 * TOL


def test_a_state_kept_in_bf16_over_512_steps_is_seen(tiny, reference, prefill):
    """The scan state is float32 by the configuration. Rounded to bfloat16
    after every step (the rest of the program as it is) it leaves the
    reference over 512 decode steps by more than ten times the tolerance,
    while the float32 state stays inside it."""
    cfg, params = tiny
    seq = np.random.default_rng(4).integers(0, cfg.vocab_size, 16 + 512)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(16, 16 + 512)], 768)[0]
    sound, _ = _decode_after_prefill(cfg, params, prefill, seq, 16, 512,
                                     rows=768)
    rounded, _ = _decode_after_prefill(
        cfg, params, prefill, seq, 16, 512, rows=768,
        each_step=lambda h: h.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(sound - want).max() < TOL
    assert np.abs(rounded - want).max() > 10 * TOL


def test_a_router_in_bf16_is_seen(tiny, reference, monkeypatch):
    """The router runs in float32 at the highest precision. With its input
    and its weights rounded to bfloat16 the softmax over the selected logits
    moves (and a near-tie picks the other expert): the logits leave the
    reference by more than ten times the tolerance."""
    cfg, params = tiny
    seq = np.random.default_rng(5).integers(0, cfg.vocab_size, 32)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(32)], 64)[0]
    route = el.route

    def rounded(h, router, *args, **kw):
        bf = jnp.bfloat16
        return route(h.astype(bf).astype(jnp.float32),
                     router.astype(bf).astype(jnp.float32), *args, **kw)

    monkeypatch.setattr(el, "route", rounded)
    worst = 0.0
    for n in (8, 16, 24, 32):
        last, _, _ = jax.jit(lambda p, t, m: sm.prefill(p, cfg, t, m))(
            params, _padded(seq, n, 32), n)
        worst = max(worst, np.abs(np.asarray(last) - want[n - 1]).max())
    assert worst > 10 * TOL


# --- the engine, two slots, reuse ----------------------------------------------

def test_the_engine_admits_two_slots_at_different_steps_and_reuses_one(
        tiny, reference):
    """ServingEngine's own prefill, insert and decode_chunk: a second request
    is admitted while the first decodes, the first finishes, and a third takes
    its slot over. Every served token is the reference's best at its
    position, and the device-summed counters say what the routers did."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    state, rows = eng.state.cache.held
    assert {k: v.shape for k, v in state.items()} == {
        "conv": (6, 3, 2, 64), "ssm": (6, 2, 16, 32)}
    assert state["ssm"].dtype == jnp.float32
    assert rows["k"].shape == (2, 2, 2, 128, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 19, 2)]
    reqs = [eng.submit(prompts[0], SamplingParams(max_new_tokens=20))]
    for _ in range(2):
        eng.step()
    assert not reqs[0].done.is_set()
    reqs.append(eng.submit(prompts[1], SamplingParams(max_new_tokens=30)))
    gauge = {}
    while not reqs[0].done.is_set():
        eng.step()
        if len(eng._active_requests()) == 2:
            gauge = {s[0]["kind"]: s[1] for fam in eng._obs_collect()
                     if fam[0] == "kukeon_engine_kv_rows" for s in fam[3]}
    assert gauge["state"] == 2 and gauge["full"] >= 5 + 19
    reqs.append(eng.submit(prompts[2], SamplingParams(max_new_tokens=12)))
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    assert reqs[2].slot == reqs[0].slot != reqs[1].slot
    for prompt, req in zip(prompts, reqs):
        seq = np.concatenate([prompt, req.generated])
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = reference.logits_at(reference_config(cfg), SEED, [seq],
                                     [pos], 128)[0]
        gaps = logits.max(-1) - logits[np.arange(len(pos)), seq[pos + 1]]
        assert gaps.max() < TOL
    held = eng.registry.get("kukeon_engine_state_slot_steps_total")
    assert held.value(what="held") == 2 * sum(
        int(labels["k"]) * n for labels, n in eng.registry.get(
            "kukeon_engine_decode_chunks_total").samples())
    assert 0 < held.value(what="active") < held.value(what="held")
    rows_read = eng.registry.get("kukeon_engine_decode_kv_rows_total")
    assert rows_read.value(what="held") > 0
    routed, hits, held, reached, pair_rows, worked, tokens = (
        eng.registry.get(name).value() for name in sm.COUNTERS)
    assert routed == 2 * tokens and 0 < hits < routed
    assert hits <= worked <= pair_rows
    assert 0 < reached <= min(held, hits)
    # prompt tokens and decode steps of all three requests, in 8 layers
    assert tokens >= 8 * (5 + 19 + 2)
    assert np.isfinite(np.asarray(eng.state.cache.held[0]["ssm"])).all()


def test_a_prefix_id_is_a_counted_miss_and_what_the_family_lacks_is_refused(
        tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    for _ in range(2):
        req = eng.submit(np.arange(1, 12), SamplingParams(max_new_tokens=2),
                         prefix_id="session-1")
        while not req.done.is_set():
            eng.step()
    assert (eng.prefix_hits, eng.prefix_misses) == (0, 2)
    assert not eng._prefix_cache and eng._prefix_cache_size == 0
    with pytest.raises(ValueError, match="KV handoff"):
        eng.submit(np.arange(1, 12), export=True)
    with pytest.raises(ValueError, match="no paged KV, int8 KV"):
        _engine(cfg, params, kv_cache_int8=True)


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
        ServingCell("ssm-moe-tiny", **args)


def test_the_cell_boots_and_answers_at_the_tiny_preset():
    from kukeon_tpu.ops import dispatch
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("ssm-moe-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None, chips=1)
    assert cell.engine.family is families.of(cell.cfg)
    assert cell.engine.family.name == "ssm_moe"
    out = cell.generate({"prompt": "hello there", "maxNewTokens": 12})
    assert out["numTokens"] == 12
    # which body each program was built with (off a TPU: XLA's)
    counts = dispatch.counts()
    assert counts[("ssd_scan", "xla")] > 0
    assert counts[("state_update", "xla")] > 0


# --- the scan -------------------------------------------------------------------

def _scan_inputs(S, H, P, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (S, H * P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (S, H)) - 3.0)
    b, cm = (jax.random.normal(k, (S, N)) for k in ks[2:4])
    a = -jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
    return x, dt, b, cm, a, jnp.ones((H,))


def _token_by_token(x, dt, b, cm, a, dskip, length):
    """The recurrence as written, one token at a time, in numpy float64:
    (y [length, I], the state [N, I] state-major)."""
    x, dt, b, cm, a, dskip = (np.asarray(v, np.float64)
                              for v in (x, dt, b, cm, a, dskip))
    H, N = a.shape[0], b.shape[1]
    P = x.shape[1] // H
    state = np.zeros((H, P, N))
    ys = []
    for t in range(length):
        xt = x[t].reshape(H, P)
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * xt)[:, :, None] * b[t][None, None])
        ys.append((state @ cm[t] + dskip[:, None] * xt).reshape(-1))
    return np.stack(ys), state.transpose(2, 0, 1).reshape(N, H * P)


@pytest.mark.parametrize("chunk, length", [
    (1, 48), (5, 48), (16, 48), (24, 48), (48, 48), (256, 48),
    (16, 1), (16, 17), (16, 40), (8, 33)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk, length):
    """Chunks that divide the 48 steps and chunks that do not (5: a last
    chunk of 3, padded), one chunk, a chunk longer than the bucket; a
    ``length`` at a chunk's edge, inside a chunk and in the first one, the
    bucket right-padded past it: the same y and the same final state, to
    float32's rounding."""
    args = _scan_inputs(48, 4, 8, 16)
    want_y, want_h = _token_by_token(*args, length)
    y, h = jax.jit(lambda *a: sd.ssd_scan(*a, length, heads=4, chunk=chunk))(
        *args)
    np.testing.assert_allclose(y[:length], want_y, atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5)
    assert h.dtype == jnp.float32 and h.shape == (16, 32)


def test_the_kernel_is_the_same_scan_in_interpret_mode():
    """The Pallas body (grid of channel blocks x chunks, a block's state
    carried from chunk to chunk, two heads of 64 sharing a tile's lanes)
    against the lax.scan body and the recurrence; on the CPU only the
    interpreter runs it (tests/test_chip_compile.py compiles it for the
    chip). 32 heads are two channel blocks, 512 steps two chunks, and the
    prompt ends inside the second."""
    x, dt, b, cm, a, dskip = _scan_inputs(512, 32, 64, 128, seed=2)
    length = 300
    dt = jnp.where(jnp.arange(512)[:, None] < length, dt, 0.0)
    want_y, want_h = sd._scan_xla(x, dt, b, cm, a, dskip, heads=32, chunk=256)
    y, h = sd.scan_kernel(x, dt, b, cm, a, dskip, heads=32, chunk=256,
                          interpret=True)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=1e-4, rtol=1e-5)
    ref_y, ref_h = _token_by_token(x, dt, b, cm, a, dskip, length)
    np.testing.assert_allclose(y[:length], ref_y, atol=2e-3, rtol=1e-4)
    np.testing.assert_allclose(h, ref_h, atol=2e-3, rtol=1e-4)
    assert not sd.kernel_runs(512, 32, 64, 128, 256, 1)     # no TPU here


def test_one_decode_step_is_one_step_of_the_scan():
    """``selective_scan.state_update`` with a decay of ONE row ([1, I]: a
    head's scalar repeated over its channels) is the next step of the
    chunked scan's recurrence, gate aside."""
    x, dt, b, cm, a, dskip = _scan_inputs(9, 4, 8, 16, seed=3)
    scan = jax.jit(lambda n: sd.ssd_scan(x, dt, b, cm, a, dskip, n, heads=4,
                                         chunk=4))
    y, h = scan(9)
    h8 = scan(8)[1]
    z = jnp.full((1, 32), 30.0)     # silu(30) = 30: the gate is a constant
    y1, h1 = ss.state_update(
        h8[None], x[8:9], jnp.repeat(dt[8:9], 8, axis=-1), z, b[8:9],
        cm[8:9], jnp.repeat(a, 8)[None], jnp.repeat(dskip, 8))
    np.testing.assert_allclose(y1[0] / 30.0, y[8], atol=1e-5)
    np.testing.assert_allclose(h1[0], h, atol=1e-5)


def test_the_update_kernel_takes_a_decay_of_one_row_in_interpret_mode():
    """The kernel of ``update_held`` with ``a`` [1, I] against the XLA body:
    the active slots' states move, an idle slot's does not."""
    M, B, N, I = 2, 4, 16, 1024
    ks = jax.random.split(jax.random.key(6), 8)
    held = jax.random.normal(ks[0], (M, B, N, I))
    c, z = (jax.random.normal(k, (B, I)) for k in ks[1:3])
    d = jax.nn.softplus(jax.random.normal(ks[3], (B, I)) - 3.0)
    b, cm = (jax.random.normal(k, (B, N)) for k in ks[4:6])
    a = -jax.random.uniform(ks[6], (1, I), minval=1.0, maxval=16.0)
    dskip = jnp.ones((I,))
    walk = ss.live_slots(jnp.array([True, False, True, True]))
    y, out = ss.update_kernel(held, 1, walk.slots, walk.live, c, d, z, b, cm,
                              a, dskip, interpret=True)
    want_y, want = ss.state_update(held[1], c, d, z, b, cm, a, dskip)
    for slot in (0, 2, 3):
        np.testing.assert_allclose(out[1, slot], want[slot], atol=1e-5)
        np.testing.assert_allclose(y[slot], want_y[slot], atol=1e-4)
    assert np.array_equal(out[1, 1], held[1, 1])
    assert np.array_equal(out[0], held[0])


# --- the router and the shares ---------------------------------------------------

def test_the_softmax_over_the_selected_logits_and_the_sigmoid_path_beside_it():
    """``softmax_selected``: the top k of the LOGITS, weighted by a softmax
    over those k alone; no bias, no scale. The sigmoid path is what it was
    (Trinity's and DeepSeek's tests hold its cases): the same call without
    the word gives the sigmoid's selection and weights."""
    router = jax.random.normal(jax.random.key(1), (32, 8)) * 32 ** -0.5
    h = jax.random.normal(jax.random.key(2), (100, 32))
    sel, w = el.route(h, router, None, 3, scoring=el.SOFTMAX_SELECTED)
    logits = np.asarray(h @ router, np.float64)
    order = np.argsort(-logits, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(sel), -1), np.sort(order, -1))
    picked = np.take_along_axis(logits, np.asarray(sel), -1)
    want = np.exp(picked) / np.exp(picked).sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    bias = 0.02 * jax.random.normal(jax.random.key(3), (8,))
    sel_s, w_s = el.route(h, router, bias, 3, scale=2.448)
    s = jax.nn.sigmoid(h @ router)
    assert np.array_equal(sel_s, jax.lax.top_k(s + bias, 3)[1])
    got = jnp.take_along_axis(s, sel_s, -1)
    np.testing.assert_allclose(
        w_s, got / (got.sum(-1, keepdims=True) + 1e-20) * 2.448, rtol=1e-6)


def test_two_shares_and_the_shared_expert_once_make_the_uncut_reference_layer(
        reference):
    """What an expert-parallel combine adds up: the routed parts of the two
    shares (experts 0-3 and 4-7 of the tiny preset's 8) plus the shared
    expert, once, are the reference's layer with every expert held; the
    shares' hits are the choices made."""
    cfg = sm.ssm_moe_tiny()
    key = jax.random.key(SEED)
    x = jax.random.normal(jax.random.key(11), (40, cfg.hidden_size))
    counted = jnp.ones(40, bool)
    c = reference.dims({**reference_config(cfg), "experts_held": [0, 8]})
    with jax.default_matmul_precision("highest"):
        whole, _tie = reference._moe(key, 0, c, "f32", jnp.float32)(x)
    parts, hits = x, 0
    for first in (0, 4):
        share = sm.SsmMoEConfig(**{**vars(cfg), "experts_held": (first, 4)})
        w = sm._draw_params(key, share)["layers"][0]
        y, n = sm._moe(x, w, share, counted)
        h = rms_norm(x, w["norm2"], cfg.rms_norm_eps)
        shared = el.swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
        parts = parts + (y - x) - cfg.residual_multiplier * shared
        hits += int(n[0])
    parts = parts + cfg.residual_multiplier * shared
    assert jnp.abs(parts - whole).max() < 1e-5
    assert hits == 40 * cfg.experts_per_token


# --- sizes ----------------------------------------------------------------------

def test_the_published_model_and_the_served_share_by_their_shapes():
    """Shapes only, nothing is allocated. Whole: 32.2 G parameters. The share
    of the benchmark's configuration (one period, 36 of 72 experts, half the
    vocabulary): 4.76 G parameters, 9.52 GB; a slot holds 9 mixers' state
    (4 MiB of float32 a mixer, exactly), their tails and one layer's rows:
    71.8 MB."""
    def count(cfg):
        tree = jax.eval_shape(lambda k: sm.init_params(k, cfg),
                              jax.random.key(0))
        return tree, sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    whole = sm.granite_4_h_small()
    assert (whole.num_mixers, whole.num_periods, whole.runs) == (36, 4, (5, 4))
    assert [i for i, t in enumerate(whole.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert (whole.d_inner, whole.conv_dim) == (8192, 8448)
    assert 32.1e9 < count(whole)[1] < 32.3e9
    cfg = sm.SsmMoEConfig(vocab_size=50176, num_layers=10,
                          experts_held=(0, 36), max_seq_len=8192)
    tree, n = count(cfg)
    assert 4.75e9 < n < 4.77e9
    layer = {kind: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        tree["layers"][i])) for kind, i in (("mamba", 0), ("attention", 5))}
    experts = 36 * 3 * 4096 * 768
    assert experts == pytest.approx(339.7e6, rel=1e-3)
    # mixer 102.3 M (or attention 41.9 M) + router 0.29 M + shared 18.9 M
    assert layer["mamba"] - experts == pytest.approx(121.5e6, rel=2e-3)
    assert layer["attention"] - experts == pytest.approx(61.1e6, rel=2e-3)
    assert tree["embed"].shape == (50176, 4096)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(tree))
    assert 9.50e9 < weights < 9.53e9
    kinds = cfg.cache_kinds(8192)
    assert [(k.name, k.rows, k.unit, k.live(100)) for k in kinds] == [
        ("state", 0, "slots", 1), ("full", 8192, "rows", 100)]
    assert kv_kinds.names(kinds) == ("conv", "k", "ssm", "v")
    shapes = kv_kinds.shapes(kinds, 32, cfg.num_kv_heads, cfg.head_dim,
                             cfg.dtype)
    state, rows = shapes.held
    assert state["conv"].shape == (9, 3, 32, 8448)
    assert state["ssm"].shape == (9, 32, 128, 8192)
    assert state["ssm"].dtype == jnp.float32
    assert 128 * 8192 * 4 == 4 << 20                # a slot and mixer
    assert rows["k"].shape == (1, 32, 8, 8192, 128)
    by_name = {name: int(np.prod(x.shape)) * x.dtype.itemsize / 32
               for h in shapes.held for name, x in h.items()}
    assert by_name["ssm"] == 9 * (4 << 20)                      # 37.7 MB
    assert by_name["conv"] == 9 * 3 * 8448 * 2                  # 0.46 MB
    assert by_name["k"] + by_name["v"] == 8192 * 4096           # 33.6 MB
    assert sum(by_name.values()) / 1e6 == pytest.approx(71.8, abs=0.1)
