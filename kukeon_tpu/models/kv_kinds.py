"""A decode cache whose layers are of several kinds.

``llama.KVCache`` is one stack: every layer holds ``S_max`` rows a slot. A
model with window layers beside full ones holds two kinds of state: a window
layer needs only its window's rows, kept as a ring (position ``p`` lives in
row ``p mod rows``), a full layer needs all ``S_max``. A family states its
kinds as data (``CacheKind``); this file is what the serving engine and the
model do with them: the shapes, how a prefill's block ``[L, 1, S, KV, D]`` is
inserted into a slot, how a decode step writes its new row, and which rows a
decode step may attend to (``valid``: a count and one excluded row).

The engine HOLDS every stack KV-major, ``[layers, B, KV, rows, D]`` (the layout
the TPU compiler gives a decode scan's carry; ``serving/engine.py``), and
swaps to the row-major view ``[layers, B, rows, KV, D]`` on its way into a
chunk's scan and back (``view``): ``append`` and the model's decode step work
on the view, ``insert`` on the held arrays.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CacheKind:
    name: str                   # the label of kukeon_engine_kv_rows{kind}
    layers: tuple[int, ...]     # the layers of a prefill's block it holds
    rows: int                   # rows a slot holds
    ring: bool = False          # row = position mod rows (else = position)

    def live(self, length: int) -> int:
        """Rows of a slot of ``length`` tokens that hold something."""
        return min(length, self.rows)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayeredKV:
    k: tuple                    # per kind
    v: tuple
    lengths: jnp.ndarray        # [B] tokens a slot holds (not rows)


def shapes(kinds, batch: int, kv_heads: int, head_dim: int, dtype) -> LayeredKV:
    """ShapeDtypeStructs of the HELD cache."""
    kv = tuple(jax.ShapeDtypeStruct(
        (len(kd.layers), batch, kv_heads, kd.rows, head_dim), dtype)
        for kd in kinds)
    return LayeredKV(k=kv, v=kv,
                     lengths=jax.ShapeDtypeStruct((batch,), jnp.int32))


def view(cache: LayeredKV) -> LayeredKV:
    """held <-> row-major view; its own inverse."""
    return LayeredKV(k=tuple(jnp.swapaxes(x, 2, 3) for x in cache.k),
                     v=tuple(jnp.swapaxes(x, 2, 3) for x in cache.v),
                     lengths=cache.lengths)


def _of_kind(kd: CacheKind, per_layer: jnp.ndarray) -> jnp.ndarray:
    """The layers of ``kd`` out of an array whose axis 0 is the model's
    layers: a slice where they are neighbours."""
    first, last = kd.layers[0], kd.layers[-1]
    if kd.layers == tuple(range(first, last + 1)):
        return per_layer[first:last + 1]
    return jnp.take(per_layer, jnp.asarray(kd.layers), axis=0)


def _block_rows(kd: CacheKind, block: jnp.ndarray, length) -> jnp.ndarray:
    """The rows of a prefill's block [L, 1, S, KV, D] that this kind keeps,
    as [layers, 1, KV, rows', D] (held layout). A ring shorter than the block
    takes, for each of its rows, the LAST position below ``length`` that
    lands there; rows no position reaches hold whatever the gather brings
    and lie past the rows ``valid`` counts."""
    part = _of_kind(kd, block)
    if kd.ring and part.shape[2] > kd.rows:
        r = jnp.arange(kd.rows)
        pos = r + kd.rows * ((length - 1 - r) // kd.rows)
        part = jnp.take(part, jnp.clip(pos, 0, part.shape[2] - 1), axis=2)
    elif part.shape[2] > kd.rows:
        part = part[:, :, :kd.rows]
    return jnp.swapaxes(part, 2, 3)


@jax.named_scope("kv_insert")
def insert(cache: LayeredKV, kinds, kv_k, kv_v, length, slot) -> LayeredKV:
    """A prefill's block into ``slot`` of the held cache, kind by kind."""
    at = (0, slot, 0, 0, 0)
    return LayeredKV(
        k=tuple(jax.lax.dynamic_update_slice(
            held, _block_rows(kd, kv_k, length), at)
            for kd, held in zip(kinds, cache.k)),
        v=tuple(jax.lax.dynamic_update_slice(
            held, _block_rows(kd, kv_v, length), at)
            for kd, held in zip(kinds, cache.v)),
        lengths=cache.lengths.at[slot].set(length))


def valid(kd: CacheKind, lengths: jnp.ndarray):
    """The rows a decode step at position ``lengths`` attends to, as numbers:
    (rows to read [B], one row among them left out [B]), which the caller
    hands to ``decode_gqa_attention`` as ``lengths`` and ``skip`` in place of
    a ``[B, rows]`` mask, so that the read can stop at the last live row. A
    full stack reads rows ``< lengths`` and leaves none out (None). A ring
    holds positions ``lengths - rows .. lengths - 1``: it reads
    ``min(lengths, rows)`` rows, and once it has wrapped the oldest of them
    has left the window and sits in the row the new token takes, ``lengths %
    rows`` (before the wrap that row is past the rows read and excludes
    nothing)."""
    if kd.ring:
        return jnp.minimum(lengths, kd.rows), lengths % kd.rows
    return lengths, None


@jax.named_scope("kv_insert")
def append(cache: LayeredKV, kinds, new_k, new_v, active) -> LayeredKV:
    """One decode step's rows [L, B, 1, KV, D] into the VIEW, one in-place
    slice write a slot and kind (``llama.cache_insert`` says why a loop);
    lengths advance where ``active``."""
    B = cache.lengths.shape[0]
    ks, vs = list(cache.k), list(cache.v)
    for i, kd in enumerate(kinds):
        nk, nv = _of_kind(kd, new_k), _of_kind(kd, new_v)
        row = cache.lengths % kd.rows if kd.ring else cache.lengths
        for b in range(B):
            at = (0, b, row[b], 0, 0)
            ks[i] = jax.lax.dynamic_update_slice(ks[i], nk[:, b:b + 1], at)
            vs[i] = jax.lax.dynamic_update_slice(vs[i], nv[:, b:b + 1], at)
    return LayeredKV(k=tuple(ks), v=tuple(vs),
                     lengths=jnp.where(active, cache.lengths + 1,
                                       cache.lengths))
