"""Rotary position embeddings (RoPE).

Split-half convention (as in the Llama reference implementations): the head
dimension is split into two halves that form the (real, imaginary) pair.
Frequencies are computed in float32; the rotation is applied in float32 and
cast back to the input dtype.

``rotate`` is the same rotation under frequencies of the caller's own (YaRN's,
``yarn_frequencies``) and in either pair layout: split-half, or interleaved
(the pair is two neighbours, ``x[2i], x[2i + 1]``), as a published model's
code has it.
"""

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies [head_dim // 2] for a RoPE of base ``theta``."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate q or k by position.

    Args:
      x: [batch, seq, heads, head_dim].
      positions: [batch, seq] absolute token positions (int32).
      theta: RoPE base frequency.

    Returns:
      Rotated array, same shape and dtype as ``x``.
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)           # [D/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]                   # [B, S, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]

    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def yarn_frequencies(dim: int, theta: float, factor: float, original_max: int,
                     beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """Inverse frequencies [dim // 2] of a RoPE stretched by YaRN: a pair
    that turns more than ``beta_fast`` times over the ``original_max``
    positions the model was trained on keeps its frequency, one that turns
    less than ``beta_slow`` times has it divided by ``factor``, and a linear
    ramp over the pairs in between mixes the two."""
    def pair_of(turns):     # the (fractional) pair that makes ``turns`` turns
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high if high != low else high + 0.001) - low), 0, 1)
    freqs = rope_frequencies(dim, theta)
    return freqs / factor * ramp + freqs * (1 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """What YaRN multiplies the attention's logits by, once for q and once for
    k: the softmax scale takes its square."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotate(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray,
           interleaved: bool = False) -> jnp.ndarray:
    """x [..., S, heads, D] by ``positions`` [..., S] under ``inv_freq``
    [D // 2]; float32 inside, x's dtype out."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(*xf.shape[:-1], -1, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)
