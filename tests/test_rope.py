"""The rotation (`ops/rope.py`) against a float64 complex rotation, and the
dense family's `_qkv` against the same three products taken by hand."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.ops.norms import rms_norm
from kukeon_tpu.ops.rope import apply_rope, rope_frequencies

THETA = 1e6     # mistral-7b-v0.3's base


def _rotated_f64(x, positions, theta):
    """Split-half convention: (x[..., :D/2], x[..., D/2:]) is the (real,
    imaginary) pair, turned by position * theta ** (-2j / D)."""
    x = np.asarray(x, np.float64)
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    turn = np.exp(1j * np.asarray(positions, np.float64)[..., None] * inv_freq)
    z = (x[..., :half] + 1j * x[..., half:]) * turn[:, :, None, :]
    return np.concatenate([z.real, z.imag], axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("position", [0, 1, 2047])
@pytest.mark.parametrize("batch, seq, heads", [(1, 1, 8), (3, 5, 2)])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_apply_rope_is_the_complex_rotation(head_dim, batch, seq, heads,
                                            position, dtype):
    x = jax.random.normal(jax.random.key(head_dim + position),
                          (batch, seq, heads, head_dim), jnp.float32).astype(dtype)
    # every row of the batch at its own position, the last at `position`
    positions = jnp.maximum(
        position - jnp.arange(batch * seq, dtype=jnp.int32)[::-1], 0
    ).reshape(batch, seq)
    got = apply_rope(x, positions, THETA)
    assert got.shape == x.shape and got.dtype == x.dtype
    want = _rotated_f64(x.astype(jnp.float32), positions, THETA)
    # float32 angles: position * inv_freq is off by position * 2 ** -23 or so
    err = 1e-5 + 1e-6 * position
    if dtype == jnp.bfloat16:
        err += np.abs(want).max() * 2.0 ** -8       # the result's own rounding
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0, atol=err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_position_zero_turns_nothing_and_a_turn_keeps_the_norm(dtype):
    x = jax.random.normal(jax.random.key(7), (2, 3, 4, 128), jnp.float32).astype(dtype)
    still = apply_rope(x, jnp.zeros((2, 3), jnp.int32), THETA)
    np.testing.assert_array_equal(np.asarray(still, np.float32),
                                  np.asarray(x, np.float32))
    turned = apply_rope(x.astype(jnp.float32),
                        jnp.full((2, 3), 2047, jnp.int32), THETA)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(turned), axis=-1),
                               np.linalg.norm(np.asarray(x, np.float32), axis=-1),
                               rtol=1e-5)


def test_rope_frequencies_fall_from_one_to_the_base():
    f = np.asarray(rope_frequencies(128, THETA))
    assert f.shape == (64,) and f.dtype == np.float32
    assert f[0] == 1.0 and np.all(np.diff(f) < 0)
    np.testing.assert_allclose(f[-1], THETA ** (-126 / 128), rtol=1e-5)


def _qkv_by_hand(x, w, c, positions):
    """What `_qkv` computes, written out: the three flat products, then the
    reshape to heads, then the rotation of q and k."""
    B, S = x.shape[:2]
    h = rms_norm(x, w["attn_norm"], c.rms_norm_eps)
    q, k, v = (llama.mm(h, w[name]) for name in ("wq", "wk", "wv"))
    q = apply_rope(q.reshape(B, S, c.num_heads, c.head_dim), positions, c.rope_theta)
    k = apply_rope(k.reshape(B, S, c.num_kv_heads, c.head_dim), positions, c.rope_theta)
    return q, k, v.reshape(B, S, c.num_kv_heads, c.head_dim)


@pytest.mark.parametrize("seq", [1, 16])
@pytest.mark.parametrize("weights", ["bf16", "int8"])
def test_qkv_moves_no_number(weights, seq):
    """`_qkv` holds its three products flat behind an optimization barrier
    (so that the TPU compiler reads wq and wk in place): an identity on the
    values. Jitted, a decode step's shape and a prefill's, the results equal
    the hand-written form's to the last bit on this backend."""
    c = dataclasses.replace(llama.llama_tiny(), dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.key(0), c)
    if weights == "int8":
        params = llama.quantize_params(params)
    w = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.key(1), (2, seq, c.hidden_size),
                          jnp.float32).astype(c.dtype)
    positions = jnp.arange(2 * seq, dtype=jnp.int32).reshape(2, seq) + 100
    got = jax.jit(lambda x, w, p: llama._qkv(x, w, c, p))(x, w, positions)
    want = jax.jit(lambda x, w, p: _qkv_by_hand(x, w, c, p))(x, w, positions)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == b.dtype == c.dtype, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


def test_qkv_has_a_gradient_through_the_barrier():
    """Training differentiates through `_qkv` (training/train_step.py,
    parallel/pipeline.py): the barrier's derivative is the hand-written
    form's."""
    c = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), c)
    w = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(2), (2, 8, c.hidden_size), c.dtype)
    positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))

    def loss(fn):
        return lambda w: sum(jnp.sum(jnp.square(t)) for t in fn(x, w, c, positions))

    got = jax.jit(jax.grad(loss(llama._qkv)))(w)
    want = jax.jit(jax.grad(loss(_qkv_by_hand)))(w)
    for name in ("attn_norm", "wq", "wk", "wv"):
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]),
                                   rtol=1e-6, atol=1e-6, err_msg=name)
