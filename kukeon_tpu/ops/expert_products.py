"""Pallas TPU grouped products for an expert layer's held stack: rows sorted
by expert against ``[count, K, N]`` matrices, at the cost of the REACHED
experts' bytes.

``jax.lax.ragged_dot`` on the chip reads a reached expert's matrix at about
half the HBM rate at 6-29 MB an expert, and a third of that in a prefill
block (PERF.md section 5, PR 49). Here the
stack stays in HBM as the model holds it, the whole leaf an operand, and the
kernel is ONE walk over the experts that have a row, in scalars from the
group offsets: an expert with no row is not on the walk, so it moves no byte
and costs no step. A reached expert's matrix comes in column chunks ``[K,
tile]`` (whole K: a chunk's product needs no accumulator), each read ONCE
however many rows the expert has, ``depth - 1`` chunks in flight across
expert boundaries while the current one is multiplied.

A chunk meets its expert's rows in windows of ``window`` rows that start at
the sublane tile below the group's first row, so a decode step's few rows an
expert are one product of ``window`` rows (at 128 rows a chunk's time on the
MXU is about half its streaming time whatever the rows) and a prefill
block's hundreds are a few. A window's rows outside the group are computed
and not stored. Rows past the last group are never written: what the output
holds there is not defined.

Two forms share the walk: ``down`` is ``x W[e]``, and ``gate_up`` reads TWO
stacks in one call and emits ``silu(x Wg[e]) * (x Wu[e])``. Products take
their operands in the activations' dtype and accumulate in float32; each
product is rounded to that dtype, ``silu`` runs in float32 and is rounded,
and so is the gate's product with ``up``: the roundings of the same chain
through ``ragged_dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
WINDOW = 128            # rows of a product (fewer where a call has fewer)
_DEPTH = 3              # chunks in flight, the current one included
# The bytes ONE chunk [K, tile] may take: large enough that a copy's start
# is small beside it, small enough that the first chunk's wait is. On the
# chip 2 MB reads within 3% of the best of 2, 4 and 8 at every cell's widths;
# 4 MB falls to 580-615 GB/s at [3072, 512] and [7168, 256] (PERF.md
# section 5, PR 49).
_CHUNK_BYTES = 2 * 1024 * 1024


def column_tile(K: int, N: int, itemsize: int,
                chunk_bytes: int = _CHUNK_BYTES) -> int:
    """Columns of a chunk: the most whole lane tiles that divide ``N`` and
    keep ``[K, tile]`` inside ``chunk_bytes`` (one lane tile at least)."""
    tiles = N // LANES
    best = 1
    for t in range(1, tiles + 1):
        if tiles % t == 0 and K * t * LANES * itemsize <= chunk_bytes:
            best = t
    return best * LANES


def supports(rows: int, K: int, N: int, dtype) -> bool:
    """Whether the kernel covers ``[rows, K]`` against ``[count, K, N]``
    (dispatcher guard): rows in whole sublane tiles of the dtype (windows
    start on them), K and N in whole lane tiles."""
    item = jnp.dtype(dtype).itemsize
    return (item in (2, 4) and rows % (32 // item) == 0
            and K % LANES == 0 and N % LANES == 0)


def _kernel(offs_ref, x_ref, *refs, tile, window, depth, align):
    *w_hbm, o_ref, w_buf, sem = refs
    fused = len(w_hbm) == 2
    count = offs_ref.shape[0] - 1
    rows = x_ref.shape[0]
    chunks = o_ref.shape[1] // tile
    f32 = jnp.float32

    # The walk over the experts that have a row, in scalars; ``count`` is
    # past the end.
    def reached_from(e):
        return jax.lax.while_loop(
            lambda e: (e < count)
            & (offs_ref[jnp.minimum(e + 1, count)]
               == offs_ref[jnp.minimum(e, count)]),
            lambda e: e + 1, e)

    def after(cursor):
        e, c = cursor
        stays = c + 1 < chunks
        return (jnp.where(stays, e, reached_from(jnp.minimum(e + 1, count))),
                jnp.where(stays, c + 1, 0))

    def copies(cursor, slot):
        e, c = cursor
        at = pl.ds(pl.multiple_of(c * tile, tile), tile)
        return [pltpu.make_async_copy(w.at[e, :, at], w_buf.at[i, slot],
                                      sem.at[i, slot])
                for i, w in enumerate(w_hbm)]

    def start(cursor, slot):
        @pl.when(cursor[0] < count)
        def _start():
            for copy in copies(cursor, slot):
                copy.start()

    # cursors[i] is the chunk multiplied i steps from now; all but the last
    # are in flight when a step begins.
    cursors = [(reached_from(jnp.int32(0)), jnp.int32(0))]
    for i in range(depth - 1):
        start(cursors[-1], i)
        cursors.append(after(cursors[-1]))

    def step(carry):
        t, cursors = carry
        slot = t % depth
        start(cursors[-1], (t + depth - 1) % depth)
        e, c = cursors[0]
        lo, hi = offs_ref[e], offs_ref[e + 1]
        base = lo // align * align
        cols = pl.ds(pl.multiple_of(c * tile, tile), tile)
        for copy in copies(cursors[0], slot):
            copy.wait()

        def product(i, _):
            r0 = pl.multiple_of(
                jnp.minimum(base + i * window, rows - window), align)
            at = pl.ds(r0, window)
            x = x_ref[at, :]
            y = jnp.dot(x, w_buf[0, slot], preferred_element_type=f32
                        ).astype(x.dtype).astype(f32)
            if fused:
                up = jnp.dot(x, w_buf[1, slot], preferred_element_type=f32
                             ).astype(x.dtype).astype(f32)
                y = (jax.nn.silu(y).astype(x.dtype).astype(f32) * up
                     ).astype(x.dtype).astype(f32)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            o_ref[at, cols] = jnp.where(
                (row >= lo) & (row < hi), y,
                o_ref[at, cols].astype(f32)).astype(o_ref.dtype)
            return _

        jax.lax.fori_loop(0, (hi - base + window - 1) // window, product,
                          None)
        return t + 1, cursors[1:] + [after(cursors[-1])]

    jax.lax.while_loop(lambda carry: carry[1][0][0] < count, step,
                       (jnp.int32(0), cursors))


@functools.partial(jax.jit, static_argnames=("interpret", "chunk_bytes",
                                             "window", "depth"))
def _products(x, stacks, offsets, *, interpret: bool = False,
              chunk_bytes: int = _CHUNK_BYTES, window: int = WINDOW,
              depth: int = _DEPTH):
    rows, K = x.shape
    count, _, N = stacks[0].shape
    item = jnp.dtype(x.dtype).itemsize
    tile = column_tile(K, N, item, chunk_bytes)
    window = min(window, rows)
    held = (len(stacks) * depth * K * tile * item   # the chunks in flight
            + rows * (K + N) * item                 # rows in, rows out
            + len(stacks) * 3 * window * tile * 4)  # a window's products
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, window=window, depth=depth,
                          align=32 // item),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(stacks),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((len(stacks), depth, K, tile), x.dtype),
            pltpu.SemaphoreType.DMA((len(stacks), depth)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(held * 1.25) + (8 << 20)),
        name="expert_products",
        interpret=interpret,
    )(offsets.astype(jnp.int32), x, *stacks)


def gate_up(x, w_gate, w_up, offsets, **how):
    """x [rows, K] sorted by expert; w_gate, w_up the held stacks [count, K,
    N], whole, as the model holds them; offsets [count + 1] int32, expert
    ``e``'s rows are ``offsets[e]:offsets[e + 1]``. Returns ``silu(x Wg[e])
    * (x Wu[e])`` [rows, N] in x's dtype; rows past ``offsets[-1]`` are not
    defined."""
    return _products(x, (w_gate, w_up), offsets, **how)


def down(x, w, offsets, **how):
    """``x W[e]`` [rows, N] for x [rows, K] sorted by expert and the held
    stack w [count, K, N], as ``gate_up`` takes them."""
    return _products(x, (w,), offsets, **how)
