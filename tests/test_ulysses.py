"""Ulysses all-to-all sequence parallelism: exact parity with reference
attention, composition with data+tensor axes, and the train-step hookup."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.ops.attention import attention_mask, attention_reference, repeat_kv
from kukeon_tpu.parallel import make_mesh, ulysses_attention


def _ref(q, k, v, positions):
    n_rep = q.shape[2] // k.shape[2]
    mask = attention_mask(positions, positions)
    return attention_reference(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), mask)


def test_ulysses_matches_reference():
    B, S, NH, NKV, D = 2, 32, 8, 4, 16
    kq, kk, kv_ = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, S, NH, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, NKV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, NKV, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ref = _ref(q, k, v, positions)

    mesh = make_mesh(seq=4, data=2)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda *a: ulysses_attention(
                a[0], a[1], a[2], q_positions=a[3], kv_positions=a[3], mesh=mesh
            )
        )(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_composes_with_tensor_axis():
    """seq=2 x tensor=2: heads shard over tensor AND re-shard over seq."""
    B, S, NH, NKV, D = 2, 16, 8, 4, 8
    kq, kk, kv_ = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (B, S, NH, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, NKV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, NKV, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ref = _ref(q, k, v, positions)

    mesh = make_mesh(seq=2, tensor=2, data=2)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda *a: ulysses_attention(
                a[0], a[1], a[2], q_positions=a[3], kv_positions=a[3], mesh=mesh
            )
        )(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_head_divisibility_rejected():
    """kv heads not divisible by the seq axis -> clear error naming ring."""
    B, S, NH, NKV, D = 2, 16, 8, 2, 8
    q = jnp.zeros((B, S, NH, D), jnp.float32)
    k = jnp.zeros((B, S, NKV, D), jnp.float32)
    v = jnp.zeros((B, S, NKV, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mesh = make_mesh(seq=4, data=2)
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="ring"):
            jax.jit(
                lambda *a: ulysses_attention(
                    a[0], a[1], a[2], q_positions=a[3], kv_positions=a[3],
                    mesh=mesh,
                )
            )(q, k, v, positions)


def test_train_step_with_ulysses_attention():
    """A llama train step with attn_impl='ulysses' over a seq-sharded mesh
    produces the same loss as the ring and plain paths."""
    import dataclasses

    from kukeon_tpu.models import llama
    from kukeon_tpu.training import create_train_state
    from kukeon_tpu.training.train_step import make_optimizer, make_train_step

    cfg = dataclasses.replace(llama.llama_tiny(), num_heads=8, num_kv_heads=4)
    losses = {}
    for impl, seq in (("ulysses", 2), ("ring", 2), ("auto", 1)):
        mesh = make_mesh(seq=seq, data=8 // seq // 2, tensor=2)
        with jax.set_mesh(mesh):
            opt = make_optimizer(warmup_steps=1, total_steps=10)
            state, opt = create_train_state(cfg, mesh, jax.random.key(0), opt)
            # use_ring_attention=False so we control attn_impl directly
            import functools

            from kukeon_tpu.training.train_step import cross_entropy_loss

            B, S = 4, 32
            tokens = jax.random.randint(jax.random.key(1), (B, S), 0,
                                        cfg.vocab_size)
            targets = jnp.roll(tokens, -1, axis=1)
            mask = jnp.ones((B, S), jnp.float32)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                         (B, S))

            @jax.jit
            def loss_fn(params, tokens, targets, mask, positions, impl=impl):
                logits, _ = llama.forward(params, cfg, tokens, positions,
                                          attn_impl=impl)
                return cross_entropy_loss(logits, targets, mask)

            losses[impl] = float(loss_fn(state.params, tokens, targets, mask,
                                         positions))
    assert losses["ulysses"] == pytest.approx(losses["auto"], rel=1e-5)
    assert losses["ring"] == pytest.approx(losses["auto"], rel=1e-5)
