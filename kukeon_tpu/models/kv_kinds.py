"""A decode cache whose layers are of several kinds.

``llama.KVCache`` is one stack: every layer holds ``S_max`` rows a slot. Other
models hold other things for a slot, and a family states them as data
(``CacheKind``):

- **rows of K and V**: a full layer holds ``S_max`` rows, a window layer only
  its window's, kept as a ring (position ``p`` lives in row ``p mod rows``);
- **rows of named arrays** (``arrays``): a row that is no K and V over KV
  heads but arrays of their own widths with NO head axis, side by side for
  every layer (a latent attention's compressed row and its indexer's key),
  ``[layers, B, rows, width]`` held and viewed alike; a decode step may
  attend only the ``select`` best of the live rows, which it finds by reading
  them all. A named array belongs to ONE kind: a block's ``block[name]`` and a
  step's ``new[name]`` carry that kind's layers and no other, so two such
  kinds of different widths lie side by side (a selecting latent layer's
  ``kidx`` and ``ckv`` beside a window layer's ring of its own latent row).
  Such a kind may be a ring too, and a ring may HOLD more rows than the
  ``window`` it attends (whole tiles): ``ops/sparse_attention.py``
  ``ring_keep`` says which, and the window decode kernel evaluates it;
- **state**: named arrays WITHOUT a row axis, of a fixed size whatever the
  slot's length (a state-space layer's convolution tail and scan state). A
  prefill leaves the arrays of its last real token, ``insert`` copies them
  into the slot, and a decode step REPLACES them: nothing is appended, no row
  is valid or not, nothing wraps.

This file is what the serving engine and the model do with both: the shapes,
how what a prefill leaves behind (its ``block``: ``k`` and ``v`` ``[L, 1, S,
KV, D]`` over the layers that hold rows, and each state array by its name) is
inserted into a slot, how a decode step writes its new row or takes its new
state, and which rows a decode step may attend to (``valid``: a count and one
excluded row).

The engine HOLDS every K / V stack KV-major, ``[layers, B, KV, rows, D]`` (the
layout the TPU compiler gives a decode scan's carry; ``serving/engine.py``),
and swaps to the row-major view ``[layers, B, rows, KV, D]`` on its way into a
chunk's scan and back (``view``): ``append`` and the model's decode step work
on the view, ``insert`` on the held arrays. A state array has one layout, the
one its kind states.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CacheKind:
    name: str                   # the label of kukeon_engine_kv_rows{kind}
    layers: tuple[int, ...]     # the layers of a prefill's K / V block it holds
    rows: int = 0               # rows of K and V a slot holds; 0: none
    ring: bool = False          # row = position mod rows (else = position)
    # What a slot holds besides: (name, shape, dtype) of arrays without a row
    # axis, the shape with None where the slots go.
    state: tuple = ()
    # What a row is made of where it is no K and V: (name, width) of arrays
    # [layers, B, rows, width], and how many of a slot's live rows a decode
    # step ATTENDS at most (0: every one). It fetches every live row whatever
    # it attends (``read``): at rows / select = 16 a stream of whole blocks
    # costs less than a gather by row.
    arrays: tuple = ()
    select: int = 0
    # Of a ring: the positions a step attends, its own among them; 0: as many
    # as the ring holds rows (the K / V ring: it holds its window, and the
    # oldest row leaves as the step's own arrives). The step's own row takes
    # part unwritten, so ``window - 1`` positions lie in the ring, which may
    # hold more.
    window: int = 0

    @property
    def unit(self) -> str:
        """What ``live`` counts: a span's ``<name>_<unit>``."""
        return "rows" if self.rows else "slots"

    def live(self, length: int) -> int:
        """Rows of a slot of ``length`` tokens that hold something; of a
        kind without rows the one state the slot holds."""
        return min(length, self.rows) if self.rows else 1

    def row_names(self) -> tuple[str, ...]:
        """The arrays a row is made of."""
        if not self.rows:
            return ()
        return tuple(name for name, _w in self.arrays) or ("k", "v")

    def read(self, length: int) -> int:
        """Rows a decode step fetches of a slot of ``length`` tokens where
        the rows are named arrays: every array of every live row (the
        selection scores the first, the attention streams the others in
        place under its threshold)."""
        return self.live(length)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayeredKV:
    held: tuple                 # per kind: its arrays by name (k, v; state)
    lengths: jnp.ndarray        # [B] tokens a slot holds (not rows)

    @property
    def k(self) -> tuple:
        """The K stacks of the kinds that hold rows, in their order."""
        return tuple(h["k"] for h in self.held if "k" in h)

    @property
    def v(self) -> tuple:
        return tuple(h["v"] for h in self.held if "v" in h)


def names(kinds) -> tuple[str, ...]:
    """The arrays of a prefill's block, in the order of its leaves."""
    return tuple(sorted({name for kd in kinds for name in kd.row_names()}
                        | {name for kd in kinds
                           for name, _shape, _dtype in kd.state}))


def shapes(kinds, batch: int, kv_heads: int, head_dim: int, dtype) -> LayeredKV:
    """ShapeDtypeStructs of the HELD cache."""
    def of(kd):
        out = {}
        if kd.rows and not kd.arrays:
            out["k"] = out["v"] = jax.ShapeDtypeStruct(
                (len(kd.layers), batch, kv_heads, kd.rows, head_dim), dtype)
        for name, width in kd.arrays:
            out[name] = jax.ShapeDtypeStruct(
                (len(kd.layers), batch, kd.rows, width), dtype)
        for name, shape, dt in kd.state:
            out[name] = jax.ShapeDtypeStruct(
                tuple(batch if n is None else n for n in shape), dt)
        return out

    return LayeredKV(held=tuple(of(kd) for kd in kinds),
                     lengths=jax.ShapeDtypeStruct((batch,), jnp.int32))


def view(cache: LayeredKV) -> LayeredKV:
    """held <-> row-major view of K and V; its own inverse. Rows of named
    arrays have no head axis to swap: held and viewed alike."""
    return LayeredKV(
        held=tuple({name: jnp.swapaxes(x, 2, 3) if name in ("k", "v") else x
                    for name, x in h.items()} for h in cache.held),
        lengths=cache.lengths)


def _of_kind(kd: CacheKind, per_layer: jnp.ndarray) -> jnp.ndarray:
    """The layers of ``kd`` out of an array whose axis 0 is the block's
    layers: a slice where they are neighbours."""
    first, last = kd.layers[0], kd.layers[-1]
    if kd.layers == tuple(range(first, last + 1)):
        return per_layer[first:last + 1]
    return jnp.take(per_layer, jnp.asarray(kd.layers), axis=0)


def _block_rows(kd: CacheKind, block: jnp.ndarray, length) -> jnp.ndarray:
    """The rows of a prefill's block [L, 1, S, KV, D] that this kind keeps,
    as [layers, 1, KV, rows', D] (held layout; a named array is the kind's
    own, [layers, 1, S, width], the held layout already). A ring shorter than
    the block takes, for each of its rows, the LAST position below ``length``
    that lands there; rows no position reaches hold whatever the gather
    brings and lie past the rows ``valid`` counts."""
    part = block if kd.arrays else _of_kind(kd, block)
    if kd.ring and part.shape[2] > kd.rows:
        r = jnp.arange(kd.rows)
        pos = r + kd.rows * ((length - 1 - r) // kd.rows)
        part = jnp.take(part, jnp.clip(pos, 0, part.shape[2] - 1), axis=2)
    elif part.shape[2] > kd.rows:
        part = part[:, :, :kd.rows]
    return part if kd.arrays else jnp.swapaxes(part, 2, 3)


@jax.named_scope("kv_insert")
def insert(cache: LayeredKV, kinds, block: dict, length, slot) -> LayeredKV:
    """What a prefill left behind into ``slot`` of the held cache, kind by
    kind: the rows each kind keeps of ``k`` and ``v``, and a state array
    whole (the prefill's has one slot where the held one has all)."""
    def of(kd, held):
        out = {}
        for name in kd.row_names():
            rows = _block_rows(kd, block[name], length)
            out[name] = jax.lax.dynamic_update_slice(
                held[name], rows, (0, slot) + (0,) * (rows.ndim - 2))
        for name, shape, _dtype in kd.state:
            at = [0] * len(shape)
            at[shape.index(None)] = slot
            out[name] = jax.lax.dynamic_update_slice(
                held[name], block[name].astype(held[name].dtype), at)
        return out

    return LayeredKV(
        held=tuple(of(kd, h) for kd, h in zip(kinds, cache.held)),
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
    if not kd.ring:
        return lengths, None
    behind = (kd.window or kd.rows) - 1
    if behind == kd.rows:       # it holds the positions behind and no other
        return jnp.minimum(lengths, kd.rows), None
    if behind == kd.rows - 1:
        return jnp.minimum(lengths, kd.rows), lengths % kd.rows
    raise ValueError(
        f"kind {kd.name!r} holds {kd.rows} rows for a window of {kd.window}: "
        "a count and one excluded row cannot say which it attends "
        "(``ops/sparse_attention.py`` ``ring_keep`` can)")


def keep(active: jnp.ndarray, new: jnp.ndarray, old: jnp.ndarray,
         axis: int) -> jnp.ndarray:
    """``new`` where a slot is ``active`` [B], else ``old``; the slots on
    ``axis``. A model applies it to a LAYER's state before writing it back
    into the stack, so that the select rides in the update's own pass and no
    second array of the stack's size is made. For a state too large to pass
    whole every step (``ssm_hybrid``'s scan state) the model leaves an idle
    slot's untouched instead (``ops/selective_scan.py`` ``update_held``) and
    keeps this for the small one (its convolution's tail)."""
    shape = [1] * new.ndim
    shape[axis] = active.shape[0]
    return jnp.where(active.reshape(shape), new, old)


@jax.named_scope("kv_insert")
def append(cache: LayeredKV, kinds, new: dict, active) -> LayeredKV:
    """One decode step's outcome into the VIEW. ``new["k"]``, ``new["v"]``
    [L, B, 1, KV, D] (a named array's [L, B, 1, width]): one in-place slice
    write a slot and kind
    (``llama.cache_insert`` says why a loop). A state array comes back from
    the step WHOLE, in the held shape, and takes the place of the one the
    step read; where a slot is not ``active`` it already holds what it held
    (the step selected it back with ``keep``, or never touched it). Lengths
    advance where ``active``."""
    B = cache.lengths.shape[0]

    def of(kd, held):
        out = {name: new[name] for name, _shape, _dtype in kd.state}
        if kd.rows:
            row = cache.lengths % kd.rows if kd.ring else cache.lengths
            rows = {name: new[name] if kd.arrays else _of_kind(kd, new[name])
                    for name in kd.row_names()}
            out.update({name: held[name] for name in rows})
            for b in range(B):
                for name, x in rows.items():
                    out[name] = jax.lax.dynamic_update_slice(
                        out[name], x[:, b:b + 1],
                        (0, b, row[b]) + (0,) * (x.ndim - 3))
        return out

    return LayeredKV(
        held=tuple(of(kd, h) for kd, h in zip(kinds, cache.held)),
        lengths=jnp.where(active, cache.lengths + 1, cache.lengths))
