"""The ssm_hybrid family (models/ssm_hybrid.py, ops/selective_scan.py, the
state kind of models/kv_kinds.py) on the CPU at its tiny preset, against the
benchmark's plain reference (logits, not tokens).

Tolerances. The preset is float32 on both sides, so program and reference
differ by the order of their sums alone: 2e-4 on logits of deviation ~1 covers
40-60 steps of a recurrence (measured 2e-6 to 2e-5). The tests of a LOST or a
ROUNDED state need the other direction: what they plant has to move the logits
by far more than that tolerance, or the comparison would be blind to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import families, kv_kinds
from kukeon_tpu.models import ssm_hybrid as sh
from kukeon_tpu.ops import selective_scan as ss
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

SEED = 7
TOL = 2e-4


def reference_config(cfg: sh.SsmHybridConfig) -> dict:
    """The keys ``benchmark/reference/ssm_hybrid.py`` reads, for a program
    config (what ``benchmark/launchers/ssm_hybrid.py`` maps the other way)."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "attn_layer_period": cfg.attn_layer_period,
        "attn_layer_offset": cfg.attn_layer_offset,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "mamba_d_state": cfg.d_state, "mamba_d_conv": cfg.d_conv,
        "mamba_dt_rank": cfg.dt_rank, "mamba_expand": cfg.expand,
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": 1,
        "tie_word_embeddings": True,
        "torch_dtype": jnp.dtype(cfg.dtype).name}


@pytest.fixture(scope="module")
def tiny():
    cfg = sh.ssm_hybrid_tiny()
    return cfg, sh.init_params(jax.random.key(SEED), cfg)


@pytest.fixture(scope="module")
def reference():
    return plugins.load("reference", "ssm_hybrid")


@pytest.fixture(scope="module")
def prefill(tiny):
    cfg, _ = tiny
    return jax.jit(lambda p, t, n: sh.prefill(p, cfg, t, n))


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


# --- (a), (c): prefill alone, right-padded, at every length -------------------

@pytest.mark.parametrize("n", [1, 2, 3, 4, 19, 32])
def test_a_right_padded_prefill_gives_the_logits_and_the_state_at_its_length(
        tiny, reference, prefill, n):
    """A prompt of n tokens in a bucket of 32 (n = 1, 2, 3: shorter than the
    convolution): the logits are the reference's at position n - 1, and what
    the prefill leaves behind is what a prefill of the same prompt WITHOUT
    padding leaves: a state that ran over the padding would differ."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 32)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(32)], 64)[0]
    last, block, counted = prefill(params, _padded(seq, n, 32), n)
    assert np.abs(np.asarray(last) - want[n - 1]).max() < TOL
    assert counted.shape == (0,)
    M, I, N = cfg.num_mixers, cfg.d_inner, cfg.d_state
    assert {k: v.shape for k, v in block.items()} == {
        "k": (2, 1, 32, 1, 16), "v": (2, 1, 32, 1, 16),
        "conv": (M, 3, 1, I), "ssm": (M, 1, N, I)}
    bucket = max(8, -(-n // 8) * 8)
    if bucket != 32:
        _, exact, _ = jax.jit(lambda p, t, m: sh.prefill(p, cfg, t, m))(
            params, _padded(seq, n, bucket), n)
        for name in ("conv", "ssm"):
            np.testing.assert_allclose(block[name], exact[name], atol=1e-5)
    # shorter than the convolution: the tail's oldest columns are zero
    assert (np.asarray(block["conv"][:, :max(0, 3 - n)]) == 0).all()
    assert np.isfinite(np.asarray(block["ssm"])).all()


def test_logits_at_every_position_of_a_prompt(tiny, reference, prefill):
    """(a) in full: the prefill at each length of one prompt against the
    reference's one forward, position by position."""
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 16)
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(16)], 64)[0]
    for n in range(1, 17):
        last, _, _ = prefill(params, _padded(seq, n, 16), n)
        assert np.abs(np.asarray(last) - want[n - 1]).max() < TOL, n


# --- (b), (f): prefill, the engine's insert, then decode ----------------------

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
        logits, new, _ = sh.decode(params, cfg, token, view, kinds, active)
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
    # the slot that is not active kept its (empty) state, and stayed finite
    state = cache.held[0]
    assert (np.asarray(state["ssm"][:, 0]) == 0).all()
    assert (np.asarray(state["conv"][:, :, 0]) == 0).all()
    assert np.abs(np.asarray(state["ssm"][:, 1])).max() > 0


def test_the_check_sees_a_lost_state(tiny, reference, prefill):
    """(f) The weights' recipe leaves the state a long memory: with the scan
    state zeroed between prefill and decode (a state that was never inserted)
    the logits of the next steps leave the reference by thousands of times the
    tolerance. A recipe with a short memory would hide that."""
    cfg, params = tiny
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 60)
    n = 19
    want = reference.logits_at(reference_config(cfg), SEED, [seq],
                               [np.arange(n, n + 8)], 64)[0]
    got, _ = _decode_after_prefill(cfg, params, prefill, seq, n, 8,
                                   spoil=jnp.zeros_like)
    assert np.abs(got - want).max() > 1000 * TOL


def test_a_state_kept_in_bf16_over_512_steps_is_seen(tiny, reference, prefill):
    """(f) The scan state is float32 by the configuration. Held in bfloat16
    between steps (rounded after every step, the rest of the program as it
    is) it leaves the reference over 512 decode steps by more than ten times
    the tolerance, while the float32 state stays inside it: the precision
    below the stated one does not pass."""
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


# --- (d): the engine, two slots, reuse ----------------------------------------

def test_the_engine_admits_two_slots_at_different_steps_and_reuses_one(
        tiny, reference):
    """ServingEngine's own prefill, insert and decode_chunk: a second request
    is admitted while the first decodes, the first finishes, and a third takes
    its slot over (its state is overwritten at insert). Every served token is
    the reference's best at its position."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    state, rows = eng.state.cache.held
    assert {k: v.shape for k, v in state.items()} == {
        "conv": (6, 3, 2, 128), "ssm": (6, 2, 8, 128)}
    assert state["ssm"].dtype == jnp.float32
    assert rows["k"].shape == (2, 2, 1, 128, 16)
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
        ServingCell("ssm-hybrid-tiny", **args)


def test_the_cell_boots_and_answers_at_the_tiny_preset():
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("ssm-hybrid-tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None, chips=1)
    assert cell.engine.family is families.of(cell.cfg)
    assert cell.engine.family.name == "ssm_hybrid"
    out = cell.generate({"prompt": "hello there", "maxNewTokens": 12})
    assert out["numTokens"] == 12


# --- (g): the scan -------------------------------------------------------------

def _scan_inputs(S, I, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    c, z = (jax.random.normal(k, (S, I)) for k in ks[:2])
    d = jax.nn.softplus(jax.random.normal(ks[2], (S, I)) - 3.0)
    b, cm = (jax.random.normal(k, (S, N)) for k in ks[3:])
    a = -jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, I))
    return c, d, z, b, cm, a, jnp.ones((I,))


def _token_by_token(c, d, z, b, cm, a, dskip, length):
    """The recurrence as written, one token at a time, in numpy float64."""
    c, d, z, b, cm, a, dskip = (np.asarray(x, np.float64)
                                for x in (c, d, z, b, cm, a, dskip))
    h = np.zeros(a.shape)
    ys = []
    for t in range(length):
        h = np.exp(d[t][None] * a) * h + (d[t] * c[t])[None] * b[t][:, None]
        y = (h * cm[t][:, None]).sum(0) + dskip * c[t]
        ys.append(y * z[t] / (1 + np.exp(-z[t])))
    return np.stack(ys), h


@pytest.mark.parametrize("chunk", [1, 5, 16, 24, 48])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk):
    """Chunks that divide the 48 steps and chunks that do not (5: a last
    chunk of 3; 16 and 24 divide; 48: one chunk): the same y and the same
    final state, to float32's rounding over 48 steps."""
    args = _scan_inputs(48, 128, 8)
    want_y, want_h = _token_by_token(*args, 48)
    y, h = jax.jit(lambda *a: ss._scan_xla(*a, chunk=chunk))(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5)


@pytest.mark.parametrize("length", [1, 17, 40])
def test_the_scan_stands_still_past_the_length(length):
    args = _scan_inputs(40, 128, 8, seed=1)
    want_y, want_h = _token_by_token(*args, length)
    y, h = jax.jit(ss.selective_scan)(*args, length)
    np.testing.assert_allclose(y[:length], want_y, atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5)


def test_the_kernel_is_the_same_scan_in_interpret_mode():
    """The Pallas body (grid of channel blocks x time chunks, the state of
    1024 channels carried from chunk to chunk) against the lax.scan body; on
    the CPU only the interpreter runs it (tests/test_chip_compile.py compiles
    it for the chip)."""
    args = _scan_inputs(64, 2048, 16, seed=2)
    want_y, want_h = ss._scan_xla(*args, chunk=16)
    y, h = ss.scan_kernel(*args, chunk=16, interpret=True)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5)
    assert ss.kernel_chunk(2048, 5120, 1) is None       # no TPU here


def test_one_decode_step_is_one_step_of_the_scan():
    c, d, z, b, cm, a, dskip = _scan_inputs(9, 128, 8, seed=3)
    y, h = jax.jit(ss.selective_scan)(c, d, z, b, cm, a, dskip, 9)
    h8 = jax.jit(ss.selective_scan)(c, d, z, b, cm, a, dskip, 8)[1]
    y1, h1 = ss.state_update(h8[None], c[8:9], d[8:9], z[8:9], b[8:9],
                             cm[8:9], a, dskip)
    np.testing.assert_allclose(y1[0], y[8], atol=1e-5)
    np.testing.assert_allclose(h1[0], h, atol=1e-5)


# --- sizes ---------------------------------------------------------------------

def test_the_published_model_is_3_03_g_parameters_and_a_slot_13_5_mb():
    """Shapes only, nothing is allocated: 6.06 GB of bf16 weights with no cut
    in depth, width or vocabulary; a slot holds 26 mixers' state and two
    layers' rows."""
    cfg = sh.jamba2_3b()
    assert (cfg.num_mixers, cfg.num_periods, cfg.runs) == (26, 2, (7, 6))
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [7, 21]
    params = jax.eval_shape(lambda k: sh.init_params(k, cfg),
                            jax.random.key(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert 3.02e9 < n < 3.04e9 and 6.0e9 < weights < 6.1e9
    kinds = cfg.cache_kinds(4096)
    assert [(k.name, k.rows, k.unit, k.live(100)) for k in kinds] == [
        ("state", 0, "slots", 1), ("full", 4096, "rows", 100)]
    assert kv_kinds.names(kinds) == ("conv", "k", "ssm", "v")
    shapes = kv_kinds.shapes(kinds, 64, cfg.num_kv_heads, cfg.head_dim,
                             cfg.dtype)
    state, rows = shapes.held
    assert state["conv"].shape == (26, 3, 64, 5120)
    assert state["ssm"].shape == (26, 64, 16, 5120)
    assert rows["k"].shape == (2, 64, 1, 4096, 128) and shapes.k == (rows["k"],)
    slot = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes.held)) / 64
    assert slot / 1e6 == pytest.approx(13.5, abs=0.1)
