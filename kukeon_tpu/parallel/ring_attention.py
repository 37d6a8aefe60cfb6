"""Ring attention: sequence-parallel exact attention over the ``seq`` mesh axis.

Long-context path (RingAttention, Liu et al. 2023 — arXiv:2310.01889, public
algorithm). Each device holds one sequence shard of Q/K/V; K/V blocks rotate
around the ring via ``lax.ppermute`` (ICI neighbor exchange) while each device
accumulates its queries' attention with an online-softmax (flash-style)
running max/sum, so the full [S, S] score matrix never materializes and
sequence length scales linearly with the number of devices.

Causality is handled by absolute positions: the position vector rotates with
its K/V block, so masking is exact regardless of ring step — no special-cased
block skipping (XLA overlaps the permute with the block compute; skipping
blocks would create load imbalance anyway).

This is an exact drop-in for :func:`kukeon_tpu.ops.attention.gqa_attention`
on seq-sharded activations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kukeon_tpu.ops.attention import NEG_INF, repeat_kv
from kukeon_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
)


def _block_update(o, m, l, q, k, v, q_pos, kv_pos, scale, n_rep):
    """One online-softmax accumulation step against a K/V block.

    o: [B, Sq, H, D] f32 running (unnormalized) output
    m: [B, H, Sq] f32 running max;  l: [B, H, Sq] f32 running sum
    k/v arrive compact ([B, Sk, NKV, D]) and are GQA-expanded here, after the
    ring transfer, so ppermute traffic stays 1/n_rep of the expanded size.
    """
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None])[:, None, :, :]  # [B,1,Sq,Sk]
    scores = jnp.where(mask, scores, NEG_INF)

    m_block = jnp.max(scores, axis=-1)                 # [B, H, Sq]
    m_new = jnp.maximum(m, m_block)
    # Renormalize previous accumulators to the new max.
    correction = jnp.exp(m - m_new)                    # [B, H, Sq]
    p = jnp.exp(scores - m_new[..., None])             # [B, H, Sq, Sk]
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def _ring_attention_local(q, k, v, q_pos, kv_pos, axis_name: str, all_axes: tuple):
    """Per-device body; runs under shard_map over ``axis_name``."""
    n = jax.lax.axis_size(axis_name)
    n_rep = q.shape[2] // k.shape[2]
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    B, Sq, H, D = q.shape
    # Fresh accumulators are device-invariant; mark them varying over every
    # manual axis so the fori_loop carry type stays fixed across iterations.
    def vary(x):
        return jax.lax.pcast(x, all_axes, to="varying")

    o = vary(jnp.zeros((B, Sq, H, D), jnp.float32))
    m = vary(jnp.full((B, H, Sq), NEG_INF, jnp.float32))
    l = vary(jnp.zeros((B, H, Sq), jnp.float32))

    def step(i, carry):
        o, m, l, k, v, kv_pos = carry
        o, m, l = _block_update(o, m, l, q, k, v, q_pos, kv_pos, scale, n_rep)
        # Rotate K/V (and their positions) to the next ring neighbor.
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
        return o, m, l, k, v, kv_pos

    o, m, l, _, _, _ = jax.lax.fori_loop(0, n, step, (o, m, l, k, v, kv_pos))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    mesh: Mesh | None = None,
    axis_name: str = AXIS_SEQ,
) -> jnp.ndarray:
    """Sequence-parallel causal GQA attention.

    Args:
      q: [B, S, NH, D]; k/v: [B, S, NKV, D] — S is the *global* sequence
        length; arrays must be (or will be constrained) seq-sharded over
        ``axis_name``.
      q_positions / kv_positions: [B, S] absolute positions.
      mesh: mesh to shard_map over; defaults to the ambient abstract mesh.

    Returns: [B, S, NH, D], same sharding as q.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()

    mesh_axes = set(mesh.axis_names)
    batch_axes = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if a in mesh_axes) or None
    head_axis = AXIS_TENSOR if AXIS_TENSOR in mesh_axes else None

    qkv_spec = P(batch_axes, axis_name, head_axis, None)
    pos_spec = P(batch_axes, axis_name)
    # Accumulators become varying ONLY over axes the inputs are sharded on;
    # axes this op never touches (e.g. ``expert``) must stay invariant or
    # shard_map's replication check rejects the out_specs.
    used = {*(batch_axes or ()), axis_name}
    if head_axis:
        used.add(head_axis)
    fn = functools.partial(
        _ring_attention_local,
        axis_name=axis_name,
        all_axes=tuple(a for a in mesh.axis_names if a in used),
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec),
        out_specs=qkv_spec,
    )(q, k, v, q_positions, kv_positions)
