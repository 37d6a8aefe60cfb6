"""Ring attention must exactly match single-device reference attention."""

import jax
import jax.numpy as jnp
import numpy as np

from kukeon_tpu.ops.attention import attention_mask, attention_reference, repeat_kv
from kukeon_tpu.parallel import make_mesh, ring_attention


def test_ring_matches_reference():
    B, S, NH, NKV, D = 2, 32, 4, 2, 16
    key = jax.random.key(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, NH, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, NKV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, NKV, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    mask = attention_mask(positions, positions)
    ref = attention_reference(
        q, repeat_kv(k, NH // NKV), repeat_kv(v, NH // NKV), mask
    )

    mesh = make_mesh(seq=8)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda *a: ring_attention(
                a[0], a[1], a[2], q_positions=a[3], kv_positions=a[3], mesh=mesh
            )
        )(q, k, v, positions)

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_seq4_with_data_axis():
    """Ring attention composes with a data axis on the same mesh."""
    B, S, NH, NKV, D = 4, 16, 2, 1, 8
    key = jax.random.key(1)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, NH, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, NKV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, NKV, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    ref = attention_reference(
        q, repeat_kv(k, NH), repeat_kv(v, NH), attention_mask(positions, positions)
    )

    mesh = make_mesh(data=2, seq=4)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda *a: ring_attention(
                a[0], a[1], a[2], q_positions=a[3], kv_positions=a[3], mesh=mesh
            )
        )(q, k, v, positions)

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
