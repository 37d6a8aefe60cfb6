"""Attention that selects its rows: a small indexer scores every earlier
position for a query, the ``topk`` best are kept, and the attention proper sees
only those (DeepSeek sparse attention over a latent cache).

    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),   s <= t
    S_t     = the min(topk, t + 1) positions of largest I(t, s)

Five ops, each a Pallas TPU kernel where ``kernels_run`` says so and the XLA
body below it everywhere else (the CPU, shapes the tiles do not divide); the
XLA body is also the kernel's reference in the tests.

- ``select_rows`` (prefill): the index scores of a block of queries against
  every key up to the block's last row, never ``[S, S]`` whole: a block's
  scores live in VMEM, the ``topk``-th largest of each row is found there by a
  descent over the bits of the float's order, and what leaves is the mask
  ``s in S_t`` as int8, laid out in key tiles ``[S / T, Q, T]``.
- ``masked_attention`` (prefill): flash attention in the expanded form under
  that mask AND causality, for a group of heads that share it: the mask tile
  is fetched once a (query block, key tile) and every head of the group runs
  under it; key tiles past a block's last row are neither fetched nor run.
- ``decode_index_scores``: one query a slot against the slot's LIVE index
  keys, read in place from the held stack; tiles past a slot's length move no
  byte.
- ``decode_attention``: the ``topk``-th largest of a slot's live index scores
  and the step's own, found by the same descent, then the absorbed form over
  ONE shared latent head: the slot's live rows are streamed in place from the
  held stack, a block at a time, and attended under ``score >= topk-th`` (the
  step's own row among them without having been written first). No sort, no
  index list, no gathered copy; a block past a slot's last live row moves no
  byte, and a slot of length 0 is not walked.
- ``window_decode_attention``: the same absorbed form with NO selection, over
  a RING of latent rows (a window layer's cache: position ``p`` in row ``p mod
  rows``): the slot's live ring rows are streamed in place and attended where
  they lie inside the window, the step's own row among them unwritten.

One selection rule for both halves: everything at or above the ``topk``-th
score, never a row at or past the length; rows that tie with the ``topk``-th
are all kept. A selection is a discontinuity: two positions whose index scores
lie within rounding of each other at the ``topk`` boundary may swap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kukeon_tpu.ops import dispatch

NEG_INF = -1e30
LANES = 128
KEY_TILE = 512          # keys of one tile of the prefill kernels and the mask
SELECT_ROWS = 128       # queries a step of the selection kernel
FLASH_ROWS = 512        # queries a step of the attention kernel
DECODE_TILE = 2048      # cache rows a step of either decode kernel
DECODE_DEPTH = 3        # blocks the decode attention's walk keeps in flight
WINDOW_TILE = 256       # ring rows a step of the window decode kernel
SUBLANES = 8            # a slot's scores lie [SUBLANES, rows / SUBLANES] in VMEM
INT_MIN = -2 ** 31


def key_tile(keys: int) -> int:
    return min(KEY_TILE, keys)


def kernels_run(queries: int, keys: int, head_dim: int) -> bool:
    """Whether the prefill kernels cover a chunk of ``queries`` rows against
    ``keys`` positions (dispatcher guard): one TPU, whole tiles."""
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1
            and keys % KEY_TILE == 0 and queries % FLASH_ROWS == 0
            and head_dim % LANES == 0)


def decode_kernel_runs(rows: int, head_dim: int) -> bool:
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1
            and rows % DECODE_TILE == 0 and head_dim % LANES == 0)


def _order(x):
    """float32 -> int32 whose signed order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def _kth_largest(count, topk: int, shape):
    """The order of the ``topk``-th largest entry, bit by bit from the sign
    down: the largest ``at`` that at least ``topk`` entries reach.
    ``count(at)`` says how many entries are >= ``at`` (``shape`` int32 both).
    Fewer entries than ``topk`` end at or under the order of -inf: everything
    there is is selected."""
    at = jnp.where(count(jnp.zeros(shape, jnp.int32)) >= topk,
                   jnp.int32(0), jnp.int32(INT_MIN))

    def descend(i, at):
        cand = at | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(cand) >= topk, cand, at)

    return jax.lax.fori_loop(0, 31, descend, at)


# --- select_rows ---------------------------------------------------------------

def _select_kernel(row0_ref, q_ref, w_ref, k_ref, mask_ref, sc_ref, *,
                   topk, heads, dim):
    nk, bq, bk = sc_ref.shape
    first = row0_ref[0] + pl.program_id(0) * bq
    # the key tiles that hold a position some row of this block may see
    tiles = jnp.minimum((first + bq + bk - 1) // bk, nk)
    rows = first + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

    def score(t, _):
        k = k_ref[t]                                            # [bk, D]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[:, h * dim:(h + 1) * dim], k,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(s, 0.0)
        acc = jnp.where(t * bk + cols <= rows, acc, -jnp.inf)
        sc_ref[t] = _order(acc)
        return 0

    jax.lax.fori_loop(0, tiles, score, 0)

    def count(at):          # rows' entries >= at [bq, 1], over the tiles
        def tile(t, c):
            ge = (sc_ref[t] >= at).astype(jnp.int32)
            for lane in range(0, bk, LANES):
                c = c + ge[:, lane:lane + LANES]
            return c

        c = jax.lax.fori_loop(0, tiles, tile,
                              jnp.zeros((bq, min(bk, LANES)), jnp.int32))
        return jnp.sum(c, axis=1, keepdims=True)

    at = _kth_largest(count, topk, (bq, 1))

    def write(t, _):
        keep = (sc_ref[t] >= at) & (t * bk + cols <= rows)
        mask_ref[t] = keep.astype(jnp.int32).astype(mask_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tiles, write, 0)

    def blank(t, _):
        mask_ref[t] = jnp.zeros((bq, bk), mask_ref.dtype)
        return 0

    jax.lax.fori_loop(tiles, nk, blank, 0)


def _select_xla(q, w, k, row0, topk):
    Q, S = q.shape[0], k.shape[0]
    s = jnp.einsum("qhd,kd->qhk", q, k, preferred_element_type=jnp.float32)
    score = jnp.einsum("qhk,qh->qk", jnp.maximum(s, 0.0), w)
    see = jnp.arange(S)[None, :] <= (row0 + jnp.arange(Q))[:, None]
    score = jnp.where(see, score, -jnp.inf)
    kth = jax.lax.top_k(score, min(topk, S))[0][:, -1:]
    T = key_tile(S)
    keep = (score >= kth) & see
    return keep.astype(jnp.int8).reshape(Q, S // T, T).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def select_rows(q, w, k, row0, *, topk: int, interpret: bool | None = None):
    """The mask ``s in S_t`` of a chunk of queries. q [Q, Hh, D] the indexer's
    queries (rotated), w [Q, Hh] float32 their weights, k [S, D] every index
    key of the prompt; the chunk's first row is position ``row0`` (traced).
    Returns int8 [S / T, Q, T] (T = ``key_tile(S)``): tile, query, key of the
    tile; 1 where the key is at or before the query and among its ``topk``
    best."""
    Q, Hh, D = q.shape
    S = k.shape[0]
    if interpret is None:
        if not kernels_run(Q, S, D):
            dispatch.note("select_rows", "xla")
            return _select_xla(q, w, k, row0, topk)
        interpret = False
    dispatch.note("select_rows", "pallas")
    bk, bq = key_tile(S), min(SELECT_ROWS, Q)
    nk = S // bk
    held = (nk * bq * bk * 4 + 2 * nk * bq * bk + 2 * S * D * k.dtype.itemsize
            + 2 * bq * Hh * (D * q.dtype.itemsize + 4 * LANES))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=Hh, dim=D),
        out_shape=jax.ShapeDtypeStruct((nk, Q, bk), jnp.int8),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Q // bq,),
            in_specs=[
                pl.BlockSpec((bq, Hh * D), lambda i, r: (i, 0)),
                pl.BlockSpec((bq, Hh), lambda i, r: (i, 0)),
                pl.BlockSpec((nk, bk, D), lambda i, r: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((nk, bq, bk), lambda i, r: (0, i, 0)),
            scratch_shapes=[pltpu.VMEM((nk, bq, bk), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(held * 1.25) + (8 << 20)),
        name="sparse_select_rows",
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), q.reshape(Q, Hh * D),
      w.astype(jnp.float32), k.reshape(nk, bk, D))


# --- masked_attention ----------------------------------------------------------

def _last_tile(row0, i, bq, bk):
    """The last key tile a query block may see."""
    return (row0 + (i + 1) * bq - 1) // bk


def _attend_kernel(row0_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale):
    G, bq, _ = q_ref.shape
    bk = k_ref.shape[1]
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= _last_tile(row0_ref[0], i, bq, bk))
    def _compute():
        keep = mask_ref[...].astype(jnp.int32) != 0             # [bq, bk]

        def head(h, _):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[h][:, :1]
            l_prev = l_scr[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a row that has met no selected key yet holds NEG_INF: it adds
            # weights of 1 here, and its first real key's ``corr`` of 0 wipes
            # them; every row selects at least one key
            p = jnp.exp(s - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[h]
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        def head(h, _):
            l = jnp.maximum(l_scr[h][:, :1], 1e-30)
            o_ref[h] = (acc_scr[h] / l).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, G, head, 0)


def _attend_xla(q, k, v, mask, scale):
    nk, Q, T = mask.shape
    keep = mask.transpose(1, 0, 2).reshape(Q, nk * T) != 0
    s = jnp.einsum("gqd,gkd->gqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep[None], s, NEG_INF), axis=-1)
    return jnp.einsum("gqk,gkd->gqd", p.astype(v.dtype), v).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def masked_attention(q, k, v, mask, row0, *, scale: float,
                     interpret: bool | None = None):
    """softmax(q k^T * scale) v over the keys the mask keeps, for a group of
    heads that share it. q [G, Q, Dk], k [G, S, Dk], v [G, S, Dv]; ``mask``
    as ``select_rows`` gives it (causality inside); ``row0`` the position of
    the chunk's first query. Returns [G, Q, Dv] in q's dtype."""
    G, Q, Dk = q.shape
    S, Dv = k.shape[1], v.shape[2]
    if interpret is None:
        if not kernels_run(Q, S, Dv):
            dispatch.note("masked_attention", "xla")
            return _attend_xla(q, k, v, mask, scale)
        interpret = False
    dispatch.note("masked_attention", "pallas")
    bk, bq = key_tile(S), min(FLASH_ROWS, Q)
    nk = S // bk

    def seen(i, j, r):
        return jnp.minimum(j, _last_tile(r[0], i, bq, bk))

    item = q.dtype.itemsize
    held = (2 * G * (bq * (Dk + Dv) + bk * (Dk + Dv)) * item + 2 * bq * bk
            + G * bq * (2 * LANES + Dv) * 4 + 4 * bq * bk * 4)
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((G, Q, Dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Q // bq, nk),
            in_specs=[
                pl.BlockSpec((G, bq, Dk), lambda i, j, r: (0, i, 0)),
                pl.BlockSpec((G, bk, Dk),
                             lambda i, j, r: (0, seen(i, j, r), 0)),
                pl.BlockSpec((G, bk, Dv),
                             lambda i, j, r: (0, seen(i, j, r), 0)),
                pl.BlockSpec((None, bq, bk),
                             lambda i, j, r: (seen(i, j, r), i, 0)),
            ],
            out_specs=pl.BlockSpec((G, bq, Dv), lambda i, j, r: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, bq, LANES), jnp.float32),    # running max
                pltpu.VMEM((G, bq, LANES), jnp.float32),    # running sum
                pltpu.VMEM((G, bq, Dv), jnp.float32),       # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(held * 1.25) + (8 << 20)),
        name="sparse_masked_attention",
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), q, k, v, mask)


# --- decode --------------------------------------------------------------------

def _decode_scores_kernel(layer_ref, len_ref, q_ref, w_ref, k_ref, o_ref):
    del layer_ref
    bk = k_ref.shape[0]
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(j * bk < n)
    def _live():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [Hh, bk]
        s = jnp.sum(w_ref[...] * jnp.maximum(s, 0.0), axis=0, keepdims=True)
        row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        o_ref[...] = jnp.where(row < n, s, -jnp.inf)

    @pl.when(j * bk >= n)
    def _past():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_index_scores(q, w, keys, layer, lengths, *,
                        interpret: bool | None = None):
    """I(t, s) of one query a slot against the rows ``s < lengths`` of the
    slot's index keys. q [B, Hh, D], w [B, Hh] float32, ``keys`` the held
    stack [layers, B, rows, D] read at ``layer`` (traced; the stack is the
    operand so that a layer is read in place). Returns float32 [B, rows],
    -inf at and past ``lengths``."""
    B, Hh, D = q.shape
    rows = keys.shape[2]
    if interpret is None:
        if not decode_kernel_runs(rows, D):
            dispatch.note("decode_index_scores", "xla")
            k = jax.lax.dynamic_index_in_dim(keys, layer, keepdims=False)
            s = jnp.einsum("bhd,bkd->bhk", q, k,
                           preferred_element_type=jnp.float32)
            s = jnp.einsum("bhk,bh->bk", jnp.maximum(s, 0.0), w)
            return jnp.where(jnp.arange(rows)[None] < lengths[:, None], s,
                             -jnp.inf)
        interpret = False
    dispatch.note("decode_index_scores", "pallas")
    bk = min(DECODE_TILE, rows)

    def live(b, j, layer, n):       # the tile read: never past the last live
        return jnp.minimum(j, jnp.maximum((n[b] + bk - 1) // bk - 1, 0))

    out = pl.pallas_call(
        _decode_scores_kernel,
        out_shape=jax.ShapeDtypeStruct((B, 1, rows), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, rows // bk),
            in_specs=[
                pl.BlockSpec((None, Hh, D), lambda b, j, la, n: (b, 0, 0)),
                pl.BlockSpec((None, Hh, 1), lambda b, j, la, n: (b, 0, 0)),
                pl.BlockSpec((None, None, bk, D),
                             lambda b, j, la, n: (la[0], b,
                                                  live(b, j, la, n), 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, bk),
                                   lambda b, j, la, n: (b, 0, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="sparse_decode_index_scores",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q, w.astype(jnp.float32)[..., None], keys)
    return out[:, 0]


def _block_walk(lat_hbm, layer, b, blocks, buf, sem):
    """The walk both decode kernels make over a slot's first ``blocks`` blocks
    of ``buf.shape[1]`` cache rows, read in place from the held stack
    ``lat_hbm`` [layers, B, rows, W] at (``layer``, ``b``) through
    ``buf.shape[0]`` buffers: ``prime()`` starts the first copies (whatever
    follows it runs under them), ``run(step)`` calls ``step(j, rows)`` for
    each block in turn with the next copies in flight."""
    depth, bk, _ = buf.shape

    def copy(j):
        return pltpu.make_async_copy(
            lat_hbm.at[layer, b, pl.ds(pl.multiple_of(j * bk, bk), bk), :],
            buf.at[j % depth], sem.at[j % depth])

    def start(j):
        @pl.when(j < blocks)
        def _start():
            copy(j).start()

    def prime():
        for j in range(depth - 1):
            start(j)

    def run(step):
        def block(j, _):
            start(j + depth - 1)
            copy(j).wait()
            step(j, buf[j % depth])
            return 0

        jax.lax.fori_loop(0, blocks, block, 0)

    return prime, run


def _attend_block(q, rows, keep, R, scale, m_scr, l_scr, acc_scr):
    """One block of latent rows [bk, W] into a slot's running softmax: the
    heads' queries q [NH, W] against it, under ``keep`` [1, bk], the rows'
    first ``R`` values mixed. Both decode kernels' step."""
    f32 = jnp.float32
    s = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=f32) * scale                     # [NH, bk]
    s = jnp.where(keep, s, NEG_INF)
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    # a slot whose own row fell out holds NEG_INF until its first kept row:
    # the weights of 1 it adds till then are wiped by that row's ``corr`` of
    # 0; every walked slot keeps at least one row
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(rows.dtype), rows[:, :R], (((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _decode_attend_kernel(layer_ref, len_ref, own_ref, q_ref, new_ref, sc_ref,
                          lat_hbm, o_ref, kept_ref, buf, sem, m_scr, l_scr,
                          acc_scr, *, topk, scale):
    slots = q_ref.shape[0]
    R = o_ref.shape[2]
    bk = buf.shape[1]
    sub, width = sc_ref.shape[1:]
    per = width // bk                   # blocks a sublane of the scores
    layer = layer_ref[0]
    f32 = jnp.float32

    # A slot that is not walked attends to its own row alone.
    o_ref[...] = jnp.broadcast_to(new_ref[:, :, :R], o_ref.shape)
    kept_ref[...] = jnp.ones_like(kept_ref)

    def total(flags):                   # [sub, width] bool -> [1, 1] int32
        return jnp.sum(jnp.sum(flags.astype(jnp.int32), axis=1, keepdims=True),
                       axis=0, keepdims=True)

    def walk(b, n):
        prime, run = _block_walk(lat_hbm, layer, b, (n + bk - 1) // bk, buf,
                                 sem)
        prime()                         # in flight under the descent
        own = own_ref[b]
        kth = _kth_largest(
            lambda at: total(sc_ref[b] >= at) + (own >= at).astype(jnp.int32),
            topk, (1, 1))
        position = (
            jax.lax.broadcasted_iota(jnp.int32, (sub, width), 0) * width
            + jax.lax.broadcasted_iota(jnp.int32, (sub, width), 1))
        own_kept = own >= kth                                   # [1, 1]
        kept = (total((sc_ref[b] >= kth) & (position < n))
                + own_kept.astype(jnp.int32))
        kept_ref[b] = jnp.broadcast_to(kept, kept_ref.shape[1:])

        # The step's own row starts the running softmax where it is kept.
        q = q_ref[b]                                            # [NH, W]
        new = new_ref[b].astype(f32)                            # [1, W]
        s_own = jnp.sum(q.astype(f32) * new, axis=1, keepdims=True) * scale
        m_scr[...] = jnp.broadcast_to(
            jnp.where(own_kept, s_own, NEG_INF), m_scr.shape)
        l_scr[...] = jnp.broadcast_to(
            jnp.where(own_kept, 1.0, 0.0).astype(f32), l_scr.shape)
        acc_scr[...] = jnp.broadcast_to(
            jnp.where(own_kept, new[:, :R], 0.0), acc_scr.shape)

        def block(j, rows):                                     # [bk, W]
            order = sc_ref[b, pl.ds(j // per, 1),
                           pl.ds(pl.multiple_of((j % per) * bk, bk), bk)]
            row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            keep = (order >= kth) & (row < n)                   # [1, bk]
            _attend_block(q, rows, keep, R, scale, m_scr, l_scr, acc_scr)

        run(block)
        o_ref[b] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)

    def slot(b, _):
        n = len_ref[b]

        @pl.when(n > 0)
        def _live():
            walk(b, n)

        return 0

    jax.lax.fori_loop(0, slots, slot, 0)


def _attend_kept_xla(q, new, keep, rows_of, scale, value_dim):
    """The absorbed attention of q [B, NH, W] over the cache rows ``rows_of``
    [B, rows, W] and the step's own row ``new`` [B, W] (the last column of
    ``keep`` [B, rows + 1])."""
    rows = rows_of.shape[1]
    s = jnp.concatenate(
        [jnp.einsum("bhw,bkw->bhk", q, rows_of,
                    preferred_element_type=jnp.float32),
         jnp.einsum("bhw,bw->bh", q, new,
                    preferred_element_type=jnp.float32)[..., None]],
        axis=-1) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None, :], s, NEG_INF), axis=-1)
    p = p.astype(rows_of.dtype)
    mix = (jnp.einsum("bhk,bkv->bhv", p[..., :rows], rows_of[..., :value_dim],
                      preferred_element_type=jnp.float32)
           + p[..., rows:].astype(jnp.float32)
           * new[:, None, :value_dim].astype(jnp.float32))
    return mix.astype(q.dtype)


def _decode_attend_xla(q, new, own, scores, latents, layer, lengths, topk,
                       scale, value_dim):
    rows = scores.shape[1]
    rows_of = jax.lax.dynamic_index_in_dim(latents, layer, keepdims=False)
    live = jnp.arange(rows)[None] < lengths[:, None]
    both = jnp.concatenate([jnp.where(live, scores, -jnp.inf), own[:, None]],
                           axis=1)
    kth = jax.lax.top_k(both, min(topk, rows + 1))[0][:, -1:]
    keep = (both >= kth) & jnp.pad(live, ((0, 0), (0, 1)),
                                   constant_values=True)        # [B, rows + 1]
    return (_attend_kept_xla(q, new, keep, rows_of, scale, value_dim),
            jnp.sum(keep, axis=1, dtype=jnp.int32))


@jax.named_scope("latent_attention")
@functools.partial(jax.jit, static_argnames=("topk", "scale", "value_dim",
                                             "block", "interpret"))
def decode_attention(q, new, own, scores, latents, layer, lengths, *,
                     topk: int, scale: float, value_dim: int,
                     block: int | None = None, interpret: bool | None = None):
    """One decode step's selection and its attention, the absorbed form over
    the rows the selection keeps. q [B, NH, W] (each head's query against a
    latent row: ``W`` wide, the ``value_dim`` latent values first, the rotated
    part after), new [B, W] the step's own row and own [B] its index score,
    scores [B, rows] the index scores of the cache rows, ``latents`` the held
    stack [layers, B, rows, W] read at ``layer`` (traced; the stack is the
    operand so that a layer is read in place), lengths [B] the rows that live
    (0: the slot is not walked and attends to its own row alone).

    A slot keeps what reaches the ``topk``-th largest of its live scores and
    ``own`` together, ties with it included, and never a row at or past its
    length. Returns ([B, NH, value_dim] in q's dtype, float32-accumulated: the
    heads' mixes of latent values, which the caller takes through the value
    half of the up-projection; int32 [B] the rows each slot kept, its own
    among them)."""
    B, NH, W = q.shape
    rows = latents.shape[2]
    lengths = lengths.astype(jnp.int32)
    width = rows // SUBLANES
    # the largest whole-lane block that divides a sublane's run of rows
    bk = block or max(
        (b for b in range(LANES, min(DECODE_TILE, width) + 1, LANES)
         if width % b == 0), default=min(DECODE_TILE, width))
    if interpret is None:
        if not (decode_kernel_runs(rows, W) and value_dim % LANES == 0
                and width % bk == 0):
            dispatch.note("decode_attention", "xla")
            return _decode_attend_xla(q, new, own, scores, latents, layer,
                                      lengths, topk, scale, value_dim)
        interpret = False
    dispatch.note("decode_attention", "pallas")
    # the scores as the descent wants them: in the floats' order, -inf where
    # no row lives, a slot's over whole vector registers
    live = jnp.arange(rows)[None] < lengths[:, None]
    order = _order(jnp.where(live, scores, -jnp.inf)).reshape(
        B, SUBLANES, width)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    item = latents.dtype.itemsize
    held = (B * NH * (W + value_dim) * item + B * rows * 4
            + DECODE_DEPTH * bk * W * item
            + NH * (2 * LANES + value_dim + 3 * bk) * 4)
    out, kept = pl.pallas_call(
        functools.partial(_decode_attend_kernel, topk=topk, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((B, NH, value_dim), q.dtype),
                   jax.ShapeDtypeStruct((B, 1, LANES), jnp.int32)),
        in_specs=[smem] * 3 + [vmem] * 3
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(vmem, vmem),
        scratch_shapes=[
            pltpu.VMEM((DECODE_DEPTH, bk, W), latents.dtype),
            pltpu.SemaphoreType.DMA((DECODE_DEPTH,)),
            pltpu.VMEM((NH, LANES), jnp.float32),           # running max
            pltpu.VMEM((NH, LANES), jnp.float32),           # running sum
            pltpu.VMEM((NH, value_dim), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(held * 1.5) + (8 << 20)),
        name="sparse_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths, _order(own),
      q, new[:, None, :], order, latents)
    return out, kept[:, 0, 0]


# --- decode over a ring, no selection -----------------------------------------

def ring_keep(lengths, rows: int, window: int):
    """[B, rows] bool: the rows of a ring of ``rows`` rows (position ``p`` in
    row ``p mod rows``) that a step at position ``lengths`` [B] attends: those
    that hold one of the ``window - 1`` positions before it (the step's own
    row, unwritten, is the window's last). The position in row ``r`` is the
    last one below ``lengths`` that lands there, ``(lengths - 1 - r) mod
    rows`` steps back from ``lengths - 1``."""
    t = lengths[:, None]
    r = jnp.arange(rows)[None]
    return (r < jnp.minimum(t, rows)) & ((t - 1 - r) % rows < window - 1)


def _window_attend_kernel(layer_ref, len_ref, q_ref, new_ref, lat_hbm, o_ref,
                          buf, sem, m_scr, l_scr, acc_scr, *, scale, behind):
    slots = q_ref.shape[0]
    R = o_ref.shape[2]
    bk = buf.shape[1]
    held = lat_hbm.shape[2]
    layer = layer_ref[0]
    f32 = jnp.float32

    # A slot that is not walked attends to its own row alone.
    o_ref[...] = jnp.broadcast_to(new_ref[:, :, :R], o_ref.shape)

    def walk(b, t):
        n = jnp.minimum(t, held)
        prime, run = _block_walk(lat_hbm, layer, b, (n + bk - 1) // bk, buf,
                                 sem)
        prime()
        # the step's own row starts the running softmax
        q = q_ref[b]                                            # [NH, W]
        new = new_ref[b].astype(f32)                            # [1, W]
        s_own = jnp.sum(q.astype(f32) * new, axis=1, keepdims=True) * scale
        m_scr[...] = jnp.broadcast_to(s_own, m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(new[:, :R], acc_scr.shape)

        def block(j, rows):
            row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            keep = row < n
            if behind < held:       # the ring holds more than the window
                keep &= jax.lax.rem(t - 1 - row, held) < behind
            _attend_block(q, rows, keep, R, scale, m_scr, l_scr, acc_scr)

        run(block)
        o_ref[b] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)

    def slot(b, _):
        t = len_ref[b]

        @pl.when(t > 0)
        def _live():
            walk(b, t)

        return 0

    jax.lax.fori_loop(0, slots, slot, 0)


def window_kernel_runs(rows: int, block: int, width: int,
                       value_dim: int) -> bool:
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1
            and rows % block == 0 and block % SUBLANES == 0
            and width % LANES == 0 and value_dim % LANES == 0)


@jax.named_scope("window_latent_attention")
@functools.partial(jax.jit, static_argnames=("window", "scale", "value_dim",
                                             "block", "interpret"))
def window_decode_attention(q, new, latents, layer, lengths, *, window: int,
                            scale: float, value_dim: int,
                            block: int | None = None,
                            interpret: bool | None = None):
    """One decode step's attention over a window layer's ring, the absorbed
    form, nothing selected. q [B, NH, W] and new [B, W] as
    ``decode_attention`` takes them; ``latents`` the held stack [layers, B,
    rows, W] of rings read at ``layer`` (traced: a layer is read in place);
    lengths [B] the TOKENS a slot holds (0: the slot is not walked and attends
    to its own row alone). A slot attends the ring rows ``ring_keep`` names
    and its own row: ``window`` positions at most; ``window - 1`` may be
    fewer than the rows the ring holds. Returns [B, NH, value_dim] in q's
    dtype, float32-accumulated."""
    B, NH, W = q.shape
    rows = latents.shape[2]
    lengths = lengths.astype(jnp.int32)
    behind = window - 1
    if behind > rows:
        raise ValueError(f"a ring of {rows} rows cannot hold the {behind} "
                         f"positions behind a window of {window}")
    bk = block or min(WINDOW_TILE, rows)
    if interpret is None:
        if not window_kernel_runs(rows, bk, W, value_dim):
            dispatch.note("window_decode_attention", "xla")
            keep = jnp.pad(ring_keep(lengths, rows, window), ((0, 0), (0, 1)),
                           constant_values=True)
            return _attend_kept_xla(
                q, new, keep,
                jax.lax.dynamic_index_in_dim(latents, layer, keepdims=False),
                scale, value_dim)
        interpret = False
    dispatch.note("window_decode_attention", "pallas")
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    item = latents.dtype.itemsize
    held = (B * NH * (W + value_dim) * item + DECODE_DEPTH * bk * W * item
            + NH * (2 * LANES + value_dim + 3 * bk) * 4)
    return pl.pallas_call(
        functools.partial(_window_attend_kernel, scale=scale, behind=behind),
        out_shape=jax.ShapeDtypeStruct((B, NH, value_dim), q.dtype),
        in_specs=[smem] * 2 + [vmem] * 2
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((DECODE_DEPTH, bk, W), latents.dtype),
            pltpu.SemaphoreType.DMA((DECODE_DEPTH,)),
            pltpu.VMEM((NH, LANES), jnp.float32),           # running max
            pltpu.VMEM((NH, LANES), jnp.float32),           # running sum
            pltpu.VMEM((NH, value_dim), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(held * 1.5) + (8 << 20)),
        name="window_latent_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths, q,
      new[:, None, :], latents)
