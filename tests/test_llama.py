"""Model correctness: shapes, cache-vs-full equivalence, causality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    logits, cache = llama.forward(params, cfg, tokens, positions)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None
    assert np.isfinite(np.asarray(logits)).all()


def test_param_count_matches(tiny):
    cfg, params = tiny
    total = sum(x.size for x in jax.tree.leaves(params))
    assert total == cfg.param_count()


def test_cached_decode_matches_full_forward(tiny):
    """Prefill + token-by-token decode must equal one full forward pass."""
    cfg, params = tiny
    B, S = 2, 12
    prefill_len = 8
    tokens = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    full_logits, _ = llama.forward(params, cfg, tokens, positions)

    cache = llama.KVCache.create(cfg, B, max_len=32)
    logits_p, cache = llama.forward(
        params, cfg, tokens[:, :prefill_len], positions[:, :prefill_len], cache
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, :prefill_len]),
        rtol=2e-4, atol=2e-4,
    )

    for t in range(prefill_len, S):
        logits_t, cache = llama.forward(
            params, cfg, tokens[:, t : t + 1], positions[:, t : t + 1], cache
        )
        np.testing.assert_allclose(
            np.asarray(logits_t[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-4, atol=2e-4,
        )
    assert int(cache.lengths[0]) == S


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    B, S = 1, 10
    tokens = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    logits_a, _ = llama.forward(params, cfg, tokens, positions)

    tokens_b = tokens.at[0, -1].set((tokens[0, -1] + 1) % cfg.vocab_size)
    logits_b, _ = llama.forward(params, cfg, tokens_b, positions)

    np.testing.assert_allclose(
        np.asarray(logits_a[:, :-1]), np.asarray(logits_b[:, :-1]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(logits_a[:, -1]), np.asarray(logits_b[:, -1]))


def test_int8_kv_cache_decode_tracks_full_forward(tiny):
    """Quantized-cache prefill + decode must track the exact full forward
    within int8 quantization noise (per-token per-head scales keep the
    relative error ~0.4% per element)."""
    cfg, params = tiny
    B, S = 2, 12
    prefill_len = 8
    tokens = jax.random.randint(jax.random.key(7), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    full_logits, _ = llama.forward(params, cfg, tokens, positions)

    cache = llama.KVCache.create(cfg, B, max_len=32, quantized=True)
    assert cache.quantized and cache.k.dtype == jnp.int8
    logits_p, cache = llama.forward(
        params, cfg, tokens[:, :prefill_len], positions[:, :prefill_len], cache
    )
    assert cache.k.dtype == jnp.int8 and cache.k_scale.dtype == jnp.float32

    got = [np.asarray(logits_p[:, t]) for t in range(prefill_len)]
    for t in range(prefill_len, S):
        logits_t, cache = llama.forward(
            params, cfg, tokens[:, t : t + 1], positions[:, t : t + 1], cache
        )
        got.append(np.asarray(logits_t[:, 0]))
    assert int(cache.lengths[0]) == S

    want = np.asarray(full_logits)
    for t in range(S):
        a, b = got[t].ravel(), want[:, t].ravel()
        cos = np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.999, f"step {t}: cosine {cos}"
        np.testing.assert_allclose(a, b, rtol=0.08, atol=0.08)


def test_int8_kv_roundtrip_error_bounded(tiny):
    cfg, _ = tiny
    x = jax.random.normal(jax.random.key(9), (2, 16, cfg.num_kv_heads, 32))
    q, s = llama.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    back = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(back - np.asarray(x))
    # Symmetric int8 rounding error <= scale/2 per element.
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-6).all()


def test_logit_positions_matches_full_head(tiny):
    """logit_positions computes the LM head at one position per sequence;
    the row must equal the same row of the full-head logits (the prefill
    fast path must not change sampled tokens)."""
    cfg, params = tiny
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    full, _ = llama.forward(params, cfg, tokens, positions)
    idx = jnp.asarray([3, S - 1], jnp.int32)
    one, _ = llama.forward(params, cfg, tokens, positions,
                           logit_positions=idx)
    assert one.shape == (B, 1, cfg.vocab_size)
    for b in range(B):
        np.testing.assert_array_equal(np.asarray(one[b, 0]),
                                      np.asarray(full[b, int(idx[b])]))
