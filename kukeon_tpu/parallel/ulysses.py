"""Ulysses sequence parallelism: all-to-all seq<->heads reshard.

The second of the framework's two sequence/context-parallel strategies
(DeepSpeed-Ulysses, Jacobs et al. 2023 — arXiv:2309.14509, public
algorithm; the first is :mod:`kukeon_tpu.parallel.ring_attention`).

Activations arrive sequence-sharded [B, S/n, H, D]. One ``all_to_all``
re-shards them so each device holds ALL positions for H/n of the heads,
full-sequence attention runs locally per head group (any local kernel —
here the XLA reference path), and a second ``all_to_all`` swaps back.

Trade-off vs ring: two all-to-alls per attention instead of an n-step
ppermute pipeline — lower latency when the interconnect does all-to-all
well (ICI does) and when n divides the head counts; ring has no head-count
constraint and overlaps transfer with compute. Both are exact.

Constraints: the per-device head counts (num_heads and num_kv_heads after
any tensor sharding) must be divisible by the ``seq`` axis size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kukeon_tpu.ops.attention import (
    attention_mask,
    attention_reference,
    repeat_kv,
)
from kukeon_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
)


def _ulysses_local(q, k, v, q_pos, kv_pos, axis_name: str):
    """Per-device body under shard_map: local arrays are [B, S/n, h, D]."""
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"ulysses needs seq axis ({n}) to divide the local head counts "
            f"(q heads {q.shape[2]}, kv heads {k.shape[2]}); use ring "
            "attention for odd head layouts"
        )
    # seq-sharded -> head-sharded: split the head axis n ways, gather the
    # full sequence for the local head group.
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name,
        split_axis=2, concat_axis=1, tiled=True,
    )
    qf, kf, vf = a2a(q), a2a(k), a2a(v)              # [B, S, h/n, D]
    q_pos_f = jax.lax.all_gather(q_pos, axis_name, axis=1, tiled=True)
    kv_pos_f = jax.lax.all_gather(kv_pos, axis_name, axis=1, tiled=True)

    mask = attention_mask(q_pos_f, kv_pos_f)
    n_rep = qf.shape[2] // kf.shape[2]
    out = attention_reference(qf, repeat_kv(kf, n_rep), repeat_kv(vf, n_rep),
                              mask)
    # head-sharded -> seq-sharded.
    return jax.lax.all_to_all(out, axis_name=axis_name,
                              split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    mesh: Mesh | None = None,
    axis_name: str = AXIS_SEQ,
) -> jnp.ndarray:
    """Sequence-parallel causal GQA attention via all-to-all.

    Same contract as :func:`kukeon_tpu.parallel.ring_attention`: S is the
    global sequence length, arrays are (or will be constrained) seq-sharded
    over ``axis_name``; returns [B, S, NH, D] with q's sharding.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    mesh_axes = set(mesh.axis_names)
    batch_axes = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if a in mesh_axes) or None
    head_axis = AXIS_TENSOR if AXIS_TENSOR in mesh_axes else None

    qkv_spec = P(batch_axes, axis_name, head_axis, None)
    pos_spec = P(batch_axes, axis_name)
    return jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec),
        out_specs=qkv_spec,
    )(q, k, v, q_positions, kv_positions)
