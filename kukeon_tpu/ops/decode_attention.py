"""Pallas TPU decode attention: one query token a slot against the slot's
LIVE cache rows only.

A decode step is bound by the cache bytes it streams. The XLA body of
:func:`kukeon_tpu.ops.attention.decode_gqa_attention` scores every row of
every slot and masks afterwards, so a step costs ``slots x rows`` whatever is
live. Here the rows a step reads follow each slot's length: the kernel is ONE
loop over the live blocks ``(slot, block)`` of all slots in turn, walked in
scalars from the per-slot counts, with its own copies from HBM, ``depth``
blocks in flight across slot boundaries. A block past a slot's last live row
is not on the walk, so it moves no byte and costs no step; a slot with
nothing to read (not active) is not on it at all and attends to its own token
alone.

The cache stays in HBM as the engine holds it, the whole stack ``[layers, B,
KV, rows, D]`` with the layer an index, and a block is ``[KV, block_rows, D]``
of one slot: every KV head of the block in one strided copy. The online softmax runs in float32 and starts from
the new token's own score (running maximum = that score, sum = 1,
accumulator = ``v_new``), which is how the new K/V take part without being
written to the cache first. Products are the cache dtype's with float32
accumulation and the probabilities are cast to the cache's dtype before the
value product, as in the XLA body; the sums are taken block by block, so a
near-tie may resolve differently.

A full layer reads rows ``< count``; a ring (``kv_kinds.valid``) reads rows
``< count`` but ``skip``: both are a count of rows and at most one excluded
row, passed as numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# The bytes the kernel's cache buffers (depth x (K + V) blocks) may take of
# VMEM; block_rows follows from it and from the shapes.
_BUFFER_BYTES = 8 * 1024 * 1024
_DEPTH = 3
_MAX_BLOCK_ROWS = 512


def block_rows(kv_heads: int, rows: int, head_dim: int, dtype) -> int:
    """Rows of one block: the largest power of two that divides ``rows``,
    stays under ``_MAX_BLOCK_ROWS`` and lets ``_DEPTH`` K and V blocks fit
    the buffer budget. Short blocks follow the lengths more closely, long
    ones amortise a copy's latency."""
    per_row = 2 * _DEPTH * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    r = _MAX_BLOCK_ROWS
    while r > 8 and (r * per_row > _BUFFER_BYTES or rows % r):
        r //= 2
    return r


def supports(heads: int, kv_heads: int, rows: int, head_dim: int,
             dtype) -> bool:
    """Whether the kernel covers a cache ``[B, KV, rows, D]`` (dispatcher
    guard): lane-wide heads, and rows that blocks of whole tiles divide."""
    return (head_dim % LANES == 0 and heads % kv_heads == 0
            and block_rows(kv_heads, rows, head_dim, dtype) >= LANES)


def _kernel(*refs, scale, rows, depth, ring, groups):
    if ring:
        layer_ref, count_ref, skip_ref, *refs = refs
    else:
        (layer_ref, count_ref, *refs), skip_ref = refs, None
    (new_ref, k_hbm, v_hbm, o_ref,
     k_buf, v_buf, sem, m_scr, l_scr, acc_scr) = refs
    slots = new_ref.shape[0]
    layer = layer_ref[0]
    f32 = jnp.float32

    # A slot with nothing to read attends to its own token alone.
    o_ref[...] = jnp.broadcast_to(new_ref[:, :, groups + 1:groups + 2, :],
                                  o_ref.shape)

    # The walk over the live blocks, slot by slot, in scalars: a cursor is
    # (slot, block of the slot); (slots, 0) is past the end.
    def blocks_of(b):
        return (count_ref[jnp.minimum(b, slots - 1)] + rows - 1) // rows

    def slot_from(b):
        return jax.lax.while_loop(
            lambda b: (b < slots) & (blocks_of(b) == 0), lambda b: b + 1, b)

    def after(cursor):
        b, j = cursor
        stays = (b < slots) & (j + 1 < blocks_of(b))
        return (jnp.where(stays, b, slot_from(jnp.minimum(b + 1, slots))),
                jnp.where(stays, j + 1, 0))

    def copies(cursor, buf):
        b, j = cursor
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        return (
            pltpu.make_async_copy(k_hbm.at[layer, b, :, at, :],
                                  k_buf.at[buf], sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[layer, b, :, at, :],
                                  v_buf.at[buf], sem.at[1, buf]),
        )

    def start(cursor, buf):
        @pl.when(cursor[0] < slots)
        def _start():
            for c in copies(cursor, buf):
                c.start()

    # cursors[i] is the block read i steps from now; all but the last are
    # in flight when a step begins.
    cursors = [(slot_from(jnp.int32(0)), jnp.int32(0))]
    for i in range(depth - 1):
        start(cursors[-1], i)
        cursors.append(after(cursors[-1]))

    def step(carry):
        t, cursors = carry
        buf = t % depth
        start(cursors[-1], (t + depth - 1) % depth)
        b, j = cursors[0]
        n = count_ref[b]
        # rows of a KV head: its query heads, then k_new, then v_new
        q = new_ref[b]                                      # [KV, Gp, D]

        @pl.when(j == 0)
        def _from_the_new_token():
            new = q.astype(f32)
            s = jnp.sum(new * new[:, groups:groups + 1, :], axis=-1,
                        keepdims=True) * scale              # [KV, Gp, 1]
            m_scr[...] = jnp.broadcast_to(s, m_scr.shape)
            l_scr[...] = jnp.ones_like(l_scr)
            acc_scr[...] = jnp.broadcast_to(
                new[:, groups + 1:groups + 2, :], acc_scr.shape)

        for c in copies(cursors[0], buf):
            c.wait()
        row = j * rows + jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)
        live = row < n
        if ring:
            live = live & (row != skip_ref[b])
        k = k_buf[buf]                                      # [KV, rows, D]
        v = v_buf[buf]
        s = jnp.einsum("kgd,krd->kgr", q, k,
                       preferred_element_type=f32) * scale  # [KV, Gp, rows]
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.einsum(
            "kgr,krd->kgd", p.astype(v.dtype), v, preferred_element_type=f32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(j == blocks_of(b) - 1)
        def _done():
            o_ref[b] = (acc_scr[...] / l_scr[:, :, :1]).astype(o_ref.dtype)

        return t + 1, cursors[1:] + [after(cursors[-1])]

    jax.lax.while_loop(lambda carry: carry[1][0][0] < slots, step,
                       (jnp.int32(0), cursors))


@functools.partial(jax.jit,
                   static_argnames=("rows", "depth", "interpret", "scale"))
def decode_attention(q, k_new, v_new, cache_k, cache_v, count, skip=None,
                     layer=0, *, rows: int | None = None,
                     depth: int = _DEPTH, interpret: bool = False,
                     scale: float | None = None):
    """q [B, 1, H, D]; k_new, v_new [B, 1, KV, D]; cache_k, cache_v the
    stacks in the HELD layout [layers, B, KV, S, D], read at ``layer`` (the
    whole stack is the operand and stays in HBM: a layer sliced out of it
    would be copied on its way into the call); count [B] int32, the rows to
    read of each slot (0: none; at most S); skip [B] one row left out, or
    None; ``scale`` what multiplies the scores (None: ``D ** -0.5``).
    Returns [B, 1, H, D] in q's dtype.

    Nothing here but the kernel runs once a layer: the walk over the live
    blocks is the kernel's own, in scalars, from ``count``. Jitted so that
    the kernel is traced once a shape and lowered once a program however
    many layers call it: lowering a ``pallas_call`` costs a boot 0.15 s a
    call site, and a family with unrolled layers has five in each of its
    three decode programs, lowered twice (``precompile``, ``warmup``)."""
    B, _, H, D = q.shape
    _, _, KV, S, _ = cache_k.shape
    G = H // KV
    if rows is None:
        rows = block_rows(KV, S, D, cache_k.dtype)
    # One operand brings the step's new rows: a KV head's query heads, then
    # its k_new, then its v_new, padded to whole sublane tiles of the dtype
    # (the scores of the rows past the query heads are computed and dropped).
    tile = 32 // jnp.dtype(q.dtype).itemsize
    Gp = -(-(G + 2) // tile) * tile
    new = jnp.concatenate(
        [q.reshape(B, KV, G, D), k_new.reshape(B, KV, 1, D),
         v_new.reshape(B, KV, 1, D),
         jnp.zeros((B, KV, Gp - G - 2, D), q.dtype)], axis=2)
    scalars = [jnp.reshape(layer, (1,)).astype(jnp.int32), count]
    if skip is not None:
        scalars.append(skip)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    item = jnp.dtype(cache_k.dtype).itemsize
    held = (2 * depth * KV * rows * D * item          # the cache buffers
            + 2 * B * KV * Gp * D * item              # new rows in, output out
            + 2 * KV * Gp * (2 * LANES + D) * 4)      # m, l, acc
    out = pl.pallas_call(
        functools.partial(_kernel,
                          scale=1.0 / (D ** 0.5) if scale is None else scale,
                          rows=rows,
                          depth=depth, ring=skip is not None, groups=G),
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, D), q.dtype),
        in_specs=[smem] * len(scalars) + [vmem] + [hbm] * 2,
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((depth, KV, rows, D), cache_k.dtype),
            pltpu.VMEM((depth, KV, rows, D), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.VMEM((KV, Gp, LANES), jnp.float32),    # running max
            pltpu.VMEM((KV, Gp, LANES), jnp.float32),    # running sum
            pltpu.VMEM((KV, Gp, D), jnp.float32),        # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(held * 1.5) + (4 << 20)),
        name="decode_attention",
        interpret=interpret,
    )(*scalars, new, cache_k, cache_v)
    return out[:, :, :G].reshape(B, 1, H, D)
