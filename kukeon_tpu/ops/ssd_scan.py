"""The scan of a Mamba-2 mixer in its chunked matrix form: the same recurrence
as ``ops/selective_scan.py``'s with a decay that is ONE scalar a head, which is
what lets a chunk of time steps be matrix products.

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t     S[h] [P, N]
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

``x`` is ``[S, I]`` with channel ``i = h * P + p`` (``heads`` heads of ``P``
channels), ``dt`` ``[S, heads]`` float32 (through its softplus already), ``B``,
``C`` ``[S, N]`` (one group: every head reads the same), ``A`` (negative) and
``D`` ``[heads]``. Over a chunk of ``Q`` steps with ``a_t = dt_t A`` and ``cum``
its running sum inside the chunk:

    L[t, s]  = exp(cum_t - cum_s)  for s <= t, else 0                   a head
    y        = ((C B^T) o L o dt_s) x  +  exp(cum_t) (C S_in)  +  D x
    S_out    = exp(cum_Q) S_in + sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s

all matrix products, none a step at a time (Mamba-2's "SSD"; the published
``mamba_chunk_size`` is ``Q``). The state is float32 and STATE-major, ``[N,
I]`` with the channels on the lanes: 128 x 8192 x 4 B = 4 MiB a slot and mixer
at the published sizes, exactly, and the layout ``selective_scan.update_held``
walks at a decode step. Nothing of the size ``[S, heads, P, N]`` is ever made:
a chunk's state is the carry.

``ssd_scan`` runs the ``S`` tokens of one prompt from a zero state and returns
every ``y`` and the state after token ``length - 1`` (past ``length`` the step
size is masked to 0: ``exp(0) = 1`` keeps the state, the input term adds
nothing). It chooses its body from what the call observes: on one TPU, with
heads of 64 channels in blocks of 16 and a bucket in whole chunks of 256, a
Pallas kernel (grid: channel block x chunk, the block's state resident in VMEM
from its first chunk to its last; per head two ``[Q, Q]`` products on the MXU
with the decay matrix formed in registers); elsewhere a ``lax.scan`` over the
chunks that computes the same products in ``jax.numpy``. Products that carry
the float32 state (``C S_in``) run at the highest precision; ``C B^T``, the
``[Q, Q]`` product and the chunk's input to the state take their operands in
the activations' dtype and accumulate in float32, as the published kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kukeon_tpu.ops import dispatch

LANES = 128
CHUNK = 256         # time steps of a chunk (mamba_chunk_size as published)
HEAD_BLOCK = 16     # heads whose state a grid step of the kernel holds
KERNEL_HEAD_DIM = 64    # the kernel pairs two heads of 64 in a tile's lanes

_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_runs(steps: int, heads: int, head_dim: int, states: int,
                chunk: int, devices: int) -> bool:
    """Whether the Pallas body runs for a prompt bucket of ``steps`` rows
    (else the ``lax.scan`` body): a TPU, one device (GSPMD does not partition
    a ``pallas_call``), heads of 64 channels in whole blocks of 16, states in
    whole lane tiles, a bucket in whole chunks that fill lane tiles."""
    return (jax.default_backend() == "tpu" and devices == 1
            and head_dim == KERNEL_HEAD_DIM and heads % HEAD_BLOCK == 0
            and states % LANES == 0 and chunk % LANES == 0
            and steps % chunk == 0)


def _chunk_cumsum(dt, a, chunk: int):
    """dt [S, H] float32, a [H] -> the running sum of dt * a inside each chunk
    of ``chunk`` steps, [S, H]."""
    S, H = dt.shape
    return jnp.cumsum((dt * a).reshape(S // chunk, chunk, H),
                      axis=1).reshape(S, H)


def _scan_xla(x, dt, b, cm, a, dskip, *, heads: int, chunk: int):
    """``lax.scan`` over chunks of ``chunk`` steps (S in whole chunks), each
    the matrix form above; x [S, I], dt [S, H] float32, b, cm [S, N] ->
    (y [S, I] float32, the state [N, I] float32)."""
    f32 = jnp.float32
    S, I = x.shape
    N, P = b.shape[1], I // heads
    n = S // chunk
    cum = _chunk_cumsum(dt, a, chunk).reshape(n, chunk, heads)
    causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]

    def one(state, xs):
        xc, dtc, bc, cc, cumc = xs      # [Q, H, P], [Q, H], [Q, N] x 2, [Q, H]
        g = jnp.einsum("tn,sn->ts", cc, bc, preferred_element_type=f32)
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  cumc[:, None] - cumc[None, :], -jnp.inf))
        m = (g[:, :, None] * decay * dtc[None]).astype(x.dtype)
        y = jnp.einsum("tsh,shp->thp", m, xc, preferred_element_type=f32)
        y = y + jnp.exp(cumc)[:, :, None] * jnp.einsum(
            "tn,nhp->thp", cc.astype(f32), state, precision=_HIGHEST)
        y = y + dskip[None, :, None] * xc.astype(f32)
        end = cumc[-1]
        xw = (xc.astype(f32) * (jnp.exp(end[None] - cumc) * dtc)[:, :, None]
              ).astype(x.dtype)
        state = jnp.exp(end)[None, :, None] * state + jnp.einsum(
            "sn,shp->nhp", bc, xw, preferred_element_type=f32)
        return state, y

    state, y = jax.lax.scan(
        one, jnp.zeros((N, heads, P), f32),
        (x.reshape(n, chunk, heads, P), dt.reshape(n, chunk, heads),
         b.reshape(n, chunk, N), cm.reshape(n, chunk, N), cum))
    return y.reshape(S, I), state.reshape(N, I)


def _kernel(x_ref, cum_ref, dt_ref, cumt_ref, dtt_ref, cm_ref, b_ref, bt_ref,
            dskip_ref, y_ref, h_ref, *, head_dim):
    """Grid (channel block, chunk), chunks innermost. The refs of one step:
    x, y [Q, 1024] (16 heads of 64); cum, dt [Q, 16] (a head's running sum
    and step size down a column) and cumt, dtt [16, Q] (the same along a
    row: the decay matrix needs both and a transpose in registers costs
    more than the bytes); cm, b [Q, N], bt [N, Q]; dskip [1, 1024];
    ``h_ref`` [N, 1024] does not move with the chunk, so it stays in VMEM
    from a block's first chunk to its last and is the recurrence's carry.
    Two heads share a tile's 128 lanes: each head's ``[Q, Q]`` matrix
    multiplies the PAIR's x (the MXU is 128 wide either way) and a select
    keeps each head's own lanes."""
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _from_zero():
        h_ref[...] = jnp.zeros_like(h_ref)

    Q, width = x_ref.shape
    cm = cm_ref[...]
    g = jax.lax.dot_general(cm, b_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)         # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    first = jax.lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) < head_dim
    cmf = cm.astype(f32)
    cum, dt = cum_ref[...], dt_ref[...]

    for pair in range(width // LANES):
        at = pl.ds(pair * LANES, LANES)
        xp = x_ref[:, at]                                        # [Q, 128]

        def lanes(v):   # [Q, heads] -> [Q, 128]: each lane its head's column
            return jnp.where(first, v[:, 2 * pair:2 * pair + 1],
                             v[:, 2 * pair + 1:2 * pair + 2])

        ys = []
        for h in (2 * pair, 2 * pair + 1):
            decay = jnp.exp(jnp.where(
                causal, cum[:, h:h + 1] - cumt_ref[h:h + 1, :], -jnp.inf))
            m = (g * decay * dtt_ref[h:h + 1, :]).astype(xp.dtype)
            ys.append(jnp.dot(m, xp, preferred_element_type=f32))
        cumc = lanes(cum)
        end = cumc[Q - 1:Q, :]
        hp = h_ref[:, at]                                        # [N, 128]
        y = (jnp.where(first, ys[0], ys[1])
             + jnp.exp(cumc) * jnp.dot(cmf, hp, precision=_HIGHEST,
                                       preferred_element_type=f32)
             + dskip_ref[:, at] * xp.astype(f32))
        y_ref[:, at] = y.astype(y_ref.dtype)
        xw = (xp.astype(f32) * (jnp.exp(end - cumc) * lanes(dt))
              ).astype(xp.dtype)
        h_ref[:, at] = jnp.exp(end) * hp + jnp.dot(
            bt_ref[...], xw, preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def scan_kernel(x, dt, b, cm, a, dskip, *, heads: int, chunk: int = CHUNK,
                interpret: bool = False):
    """The Pallas body of ``ssd_scan``: x [S, I] and b, cm [S, N] in the
    activations' dtype, dt [S, heads] float32 (masked), a, dskip [heads]
    float32 -> (y [S, I] in x's dtype, the state [N, I] float32)."""
    f32 = jnp.float32
    S, I = x.shape
    N, P = b.shape[1], I // heads
    width = HEAD_BLOCK * P
    blocks = heads // HEAD_BLOCK
    cum = _chunk_cumsum(dt, a, chunk)

    def by_block(v):    # [S, heads] -> [blocks, S, 16]: a block's own columns
        return jnp.swapaxes(v.reshape(S, blocks, HEAD_BLOCK), 0, 1)

    columns = pl.BlockSpec((None, chunk, HEAD_BLOCK), lambda i, j: (i, j, 0))
    rows = pl.BlockSpec((HEAD_BLOCK, chunk), lambda i, j: (i, j))
    by_time = pl.BlockSpec((chunk, N), lambda i, j: (j, 0))
    channels = pl.BlockSpec((chunk, width), lambda i, j: (j, i))
    y, h = pl.pallas_call(
        functools.partial(_kernel, head_dim=P),
        grid=(blocks, S // chunk),
        in_specs=[channels, columns, columns, rows, rows, by_time, by_time,
                  pl.BlockSpec((N, chunk), lambda i, j: (0, j)),
                  pl.BlockSpec((1, width), lambda i, j: (0, i))],
        out_specs=[channels, pl.BlockSpec((N, width), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((S, I), x.dtype),
                   jax.ShapeDtypeStruct((N, I), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssd_scan",
        interpret=interpret,
    )(x, by_block(cum), by_block(dt), cum.T, dt.T, cm, b, b.T,
      jnp.repeat(dskip.astype(f32), P)[None])
    return y, h


@jax.named_scope("ssd_scan")
def ssd_scan(x, dt, b, cm, a, dskip, length, *, heads: int,
             chunk: int = CHUNK):
    """One prompt from a zero state: x [S, I] (``heads`` heads of I / heads
    channels); dt [S, heads] float32; b, cm [S, N]; a, dskip [heads];
    ``length`` of the S tokens are real. Returns (y [S, I] in x's dtype, the
    skip ``D x`` in it and no gate, and the state [N, I] float32 after token
    ``length - 1``); y past ``length`` is the padding's and means nothing."""
    S, I = x.shape
    f32 = jnp.float32
    dt = jnp.where(jnp.arange(S)[:, None] < length, dt.astype(f32), 0.0)
    a, dskip = a.astype(f32), dskip.astype(f32)
    b, cm = b.astype(x.dtype), cm.astype(x.dtype)
    chunk = min(chunk, S)
    if kernel_runs(S, heads, I // heads, b.shape[1], chunk,
                   jax.sharding.get_abstract_mesh().size):
        dispatch.note("ssd_scan", "pallas")
        return scan_kernel(x, dt, b, cm, a, dskip, heads=heads, chunk=chunk)
    dispatch.note("ssd_scan", "xla")
    pad = -S % chunk    # a last chunk that time does not fill: step size 0
    if pad:
        x, dt, b, cm = (jnp.pad(v, ((0, pad), (0, 0)))
                        for v in (x, dt, b, cm))
    y, h = _scan_xla(x, dt, b, cm, a, dskip, heads=heads, chunk=chunk)
    return y[:S].astype(x.dtype), h
