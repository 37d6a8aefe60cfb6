"""How the contiguous decode state holds K and V (engine._kv_major): with KV
heads major to rows, [L, B, KV, S_max, D], sharded on the KV axis where it
now lies. What the layout must not change: every row lies where the model's
own forward puts it, and a request's greedy tokens are what that forward
gives one step at a time. The reference here is models/llama.py alone, on a
row-major cache of one sequence; no second layout lives in the engine."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.parallel import sharding as shd
from kukeon_tpu.serving import SamplingParams, ServingEngine

SLOTS, ROWS, BUCKET = 3, 128, 64
CASES = [(1, False), (1, True), (2, False), (2, True)]
IDS = ["t1-bf16kv", "t1-int8kv", "t2-bf16kv", "t2-int8kv"]

_engines: dict = {}


def _engine(tensor: int, kv_int8: bool):
    """One engine a case, shared by the tests of this module (the programs
    compile once); every test starts from an empty state."""
    if (tensor, kv_int8) not in _engines:
        cfg = llama.llama_tiny()
        params = llama.init_params(jax.random.key(7), cfg)
        mesh = make_mesh(tensor=tensor, devices=jax.devices()[:tensor])
        eng = ServingEngine(cfg, params, mesh, num_slots=SLOTS,
                            max_seq_len=ROWS, decode_chunk=16,
                            kv_cache_int8=kv_int8, prefill_buckets=(BUCKET,))
        _engines[tensor, kv_int8] = (eng, cfg, params)
    eng, cfg, params = _engines[tensor, kv_int8]
    with jax.set_mesh(eng.mesh):
        eng.state = eng._init_state()
    return eng, cfg, params


@functools.cache
def _forward(cfg):
    """models/llama.py's forward, jitted once a configuration."""
    return jax.jit(lambda params, tokens, positions, cache: llama.forward(
        params, cfg, tokens, positions, cache))


def _stepwise(cfg, params, prompt, n_new, kv_int8):
    """(greedy tokens, row-major cache [L, 1, ROWS, KV, D]) by the model's
    forward alone: the prompt in one pass (padded to the engine's bucket, so
    that the arithmetic is the same), the block quantized as it lands if the
    cache is int8, then one token at a time."""
    n = len(prompt)
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :n] = prompt
    forward = _forward(cfg)
    logits, block = forward(
        params, jnp.asarray(padded),
        jnp.arange(BUCKET, dtype=jnp.int32)[None, :],
        llama.KVCache.create(cfg, 1, BUCKET))
    toks = [int(jnp.argmax(logits[0, n - 1]))]
    cache = llama.KVCache.create(cfg, 1, ROWS, quantized=kv_int8)
    k, v, ks, vs = block.k, block.v, None, None
    if kv_int8:
        (k, ks), (v, vs) = llama.quantize_kv(k), llama.quantize_kv(v)
        ks = cache.k_scale.at[:, :, :BUCKET].set(ks)
        vs = cache.v_scale.at[:, :, :BUCKET].set(vs)
    cache = llama.KVCache(
        k=cache.k.at[:, :, :BUCKET].set(k), v=cache.v.at[:, :, :BUCKET].set(v),
        lengths=jnp.full((1,), n, jnp.int32), k_scale=ks, v_scale=vs)
    for i in range(n_new - 1):
        logits, cache = forward(
            params, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([[n + i]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0, 0])))
    return toks, cache


def _rows(x, scale, slot, n):
    """Rows [0, n) of ``slot`` as float32 [L, n, KV, D], from a row-major
    [L, B, S, KV, D] array (dequantized where it has scales)."""
    rows = np.asarray(x, np.float32)[:, slot, :n]
    if scale is not None:
        rows = rows * np.asarray(scale)[:, slot, :n, :, None]
    return rows


@pytest.mark.parametrize("tensor,kv_int8", CASES, ids=IDS)
def test_state_holds_k_and_v_kv_major_and_sharded_on_the_kv_axis(
        tensor, kv_int8):
    eng, cfg, _ = _engine(tensor, kv_int8)
    held = (cfg.num_layers, SLOTS, cfg.num_kv_heads, ROWS, cfg.head_dim)
    scales = (cfg.num_layers, SLOTS, ROWS, cfg.num_kv_heads)
    spec = (None, None, shd.AXIS_TENSOR, None, None)

    def check(cache):
        for x in (cache.k, cache.v):
            assert x.shape == held
            assert x.dtype == (jnp.int8 if kv_int8 else cfg.dtype)
            assert tuple(x.sharding.spec) == spec
            # each device holds its own KV heads and every row of them
            assert x.sharding.shard_shape(x.shape) == (
                held[0], held[1], held[2] // tensor, held[3], held[4])
        assert cache.quantized == kv_int8
        for s in (cache.k_scale, cache.v_scale):
            if kv_int8:                         # the scales stay row-major
                assert s.shape == scales
                assert tuple(s.sharding.spec) == (
                    None, None, None, shd.AXIS_TENSOR)

    check(eng.state.cache)
    check(eng._abstract_state().cache)
    assert tuple(eng._state_shardings().cache.k.spec) == spec
    # blocks between prefill, prefix store and insert stay row-major
    assert tuple(eng._cache_shardings()[0].spec) == (
        None, None, None, shd.AXIS_TENSOR, None)
    eng.generate(np.arange(2, 30, dtype=np.int32),
                 SamplingParams(max_new_tokens=20))
    check(eng.state.cache)                      # handed on as it was held


@pytest.mark.parametrize("tensor,kv_int8", CASES, ids=IDS)
def test_every_held_row_is_the_forwards_after_insert_and_two_chunks(
        tensor, kv_int8):
    """prefill + insert into slots 2 and 0, a 4-step and a 16-step chunk,
    by the three programs themselves; slot 1 is never live."""
    eng, cfg, params = _engine(tensor, kv_int8)
    prompts = {2: np.arange(5, 42, dtype=np.int32),
               0: np.arange(90, 101, dtype=np.int32)}
    f32, i32 = jnp.float32, jnp.int32
    firsts = {}
    with jax.set_mesh(eng.mesh):
        for slot, prompt in prompts.items():
            tokens = np.zeros((1, BUCKET), np.int32)
            tokens[0, :len(prompt)] = prompt
            first, kv_k, kv_v = eng._prefill(
                eng.params, jnp.asarray(tokens), len(prompt),
                jax.random.key(1), f32(0), i32(0), f32(1))
            assert kv_k.shape == (cfg.num_layers, 1, BUCKET,
                                  cfg.num_kv_heads, cfg.head_dim)
            eng.state = eng._insert(
                eng.state, kv_k, kv_v, len(prompt), slot, first)
            firsts[slot] = int(first)
        greedy = (jnp.zeros((SLOTS,), f32), jnp.zeros((SLOTS,), i32),
                  jnp.ones((SLOTS,), f32))
        eng.state, toks4 = eng._decode_chunk(
            eng.params, eng.state, jax.random.key(2), *greedy, 4)
        eng.state, toks16 = eng._decode_chunk(
            eng.params, eng.state, jax.random.key(3), *greedy, 16)
    served = np.concatenate([np.asarray(toks4), np.asarray(toks16)], axis=1)
    assert served.shape == (SLOTS, 20)

    cache = eng.state.cache
    held_k = np.swapaxes(np.asarray(cache.k), 2, 3)      # row-major again
    held_v = np.swapaxes(np.asarray(cache.v), 2, 3)
    assert list(np.asarray(cache.lengths)) == [11 + 20, 0, 37 + 20]
    # a slot that is not live writes its step's row at its length, 0
    assert not held_k[:, 1, 1:].any() and not held_v[:, 1, 1:].any()
    atol = 0.05 if kv_int8 else 1e-4
    for slot, prompt in prompts.items():
        want_toks, ref = _stepwise(cfg, params, prompt, 21, kv_int8)
        assert [firsts[slot], *served[slot]] == want_toks
        n = len(prompt) + 20
        for got, got_s, want, want_s in (
                (held_k, cache.k_scale, ref.k, ref.k_scale),
                (held_v, cache.v_scale, ref.v, ref.v_scale)):
            np.testing.assert_allclose(
                _rows(got, got_s, slot, n), _rows(want, want_s, 0, n),
                atol=atol, rtol=0)
            assert _rows(got, got_s, slot, n).any(axis=(0, 2, 3)).all(), \
                "a row of a live slot was never written"


@pytest.mark.parametrize("tensor,kv_int8", CASES, ids=IDS)
def test_greedy_tokens_are_the_stepwise_forwards(tensor, kv_int8):
    """Through the engine's own loop (admission, chunks of 16 and the
    clamped ones near max_new_tokens), more requests than slots."""
    eng, cfg, params = _engine(tensor, kv_int8)
    prompts = [np.arange(3 + 7 * i, 20 + 9 * i, dtype=np.int32)
               for i in range(SLOTS + 1)]
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=37)) for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    for prompt, req in zip(prompts, reqs):
        assert req.error is None
        want, _ = _stepwise(cfg, params, prompt, 37, kv_int8)
        assert req.generated == want
