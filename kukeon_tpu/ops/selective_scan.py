"""The selective scan of a Mamba-1 mixer: a recurrence over time whose decay
turns on the input.

    H_t = exp(d_t * A) * H_{t-1} + (d_t * c_t) * B_t        H [N, I], float32
    y_t = (sum_n H_t[n] * C_t[n] + D * c_t) * silu(z_t)

``c``, ``z`` are ``[.., I]`` (the convolved input and the gate), ``d`` ``[..,
I]`` float32 (the step size, already through its softplus), ``B``, ``C`` ``[..,
N]``, ``A`` ``[N, I]`` (negative), ``D`` ``[I]``. The state is held STATE-major,
``[N, I]`` with the channels on the lanes: an ``[I, 16]`` array would pad its
16 to a tile's 128 lanes and take eight times its bytes on a TPU.

Two entry points. ``selective_scan`` runs the ``S`` tokens of one prompt from a
zero state and returns every ``y`` and the state after token ``length - 1``
(past ``length`` the step size is masked to 0, so ``exp(0) = 1`` keeps the
state and the input term adds nothing: a prompt right-padded to its bucket
leaves the state of its last real token). It chooses its body from what the
call observes, as ``ops/attention.py`` does: on one TPU, with channels that
fill whole tiles, a Pallas kernel that walks time in chunks with the state of
1024 channels in registers, so that HBM sees ``c``, ``d``, ``z``, ``y`` once
and ``B``, ``C`` once a channel block and never an ``[S, I, N]`` array;
elsewhere a ``lax.scan`` over chunks of time steps that computes the same.
``state_update`` is one step for ``B`` slots (a decode step): plain
``jax.numpy`` that XLA fuses into one pass over the held state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kukeon_tpu.ops import dispatch

SUBLANES, LANES = 8, 128
BLOCK = SUBLANES * LANES    # channels whose state a kernel step holds
CHUNK = 256                 # time steps a grid step of the kernel walks
XLA_CHUNK = 16              # time steps a step of the lax.scan body unrolls


def kernel_chunk(steps: int, channels: int, devices: int) -> int | None:
    """The time steps a grid step of the kernel walks for a prompt bucket of
    ``steps`` rows, or None where the ``lax.scan`` body runs: the kernel needs
    a TPU, one device (GSPMD does not partition a ``pallas_call``), channels
    in whole blocks of 1024 and a bucket its chunks divide."""
    if (jax.default_backend() != "tpu" or devices > 1 or channels % BLOCK
            or steps % SUBLANES):
        return None
    chunk = min(CHUNK, steps)
    return chunk if steps % chunk == 0 else None


def _gate(y, z):
    return y * z * jax.nn.sigmoid(z)


def _step(h, c, d, z, b, cm, a, dskip):
    """One time step for any leading axes: h [.., N, I]; c, d, z [.., I];
    b, cm [.., N]."""
    h = jnp.exp(d[..., None, :] * a) * h \
        + (d * c)[..., None, :] * b[..., :, None]
    y = jnp.sum(h * cm[..., :, None], axis=-2) + dskip * c
    return h, _gate(y, z)


def _scan_xla(c, d, z, b, cm, a, dskip, chunk: int):
    """``lax.scan`` over chunks of ``chunk`` time steps, each unrolled; a
    last chunk that time does not fill runs on with step size 0."""
    S, I = c.shape
    pad = -S % chunk
    xs = [jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[-1])
          for x in (c, d, z, b, cm)]

    def walk(h, x):
        ys = []
        for t in range(chunk):
            h, y = _step(h, *(v[t] for v in x), a, dskip)
            ys.append(y)
        return h, jnp.stack(ys)

    h, y = jax.lax.scan(walk, jnp.zeros(a.shape, jnp.float32), xs)
    return y.reshape(-1, I)[:S], h


def _kernel(b_ref, cm_ref, c_ref, d_ref, z_ref, a_ref, dskip_ref, y_ref,
            h_ref, *, chunk, states):
    """Grid (channel block, time chunk), time innermost. The refs of one step:
    c, d, z, y [chunk, 8, 128] (1024 channels a time step, one register each);
    a, h [N, 8, 128]; b, cm in SMEM, [chunk * N] scalars. ``h_ref``'s block
    does not move with the time chunk, so it stays in VMEM from a channel
    block's first chunk to its last and is the recurrence's carry."""
    @pl.when(pl.program_id(1) == 0)
    def _from_zero():
        h_ref[...] = jnp.zeros_like(h_ref)

    dskip = dskip_ref[...]

    def step(t, h):
        c, d, z = c_ref[t], d_ref[t], z_ref[t]
        dc = d * c
        y = dskip * c
        out = []
        for n in range(states):
            hn = jnp.exp(d * a_ref[n]) * h[n] + dc * b_ref[t * states + n]
            y = y + hn * cm_ref[t * states + n]
            out.append(hn)
        y_ref[t] = _gate(y, z)
        return tuple(out)

    h = jax.lax.fori_loop(0, chunk, step,
                          tuple(h_ref[n] for n in range(states)))
    for n in range(states):
        h_ref[n] = h[n]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def scan_kernel(c, d, z, b, cm, a, dskip, *, chunk: int,
                interpret: bool = False):
    """The Pallas body of ``selective_scan``: float32 operands, c, d, z [S, I],
    b, cm [S, N], a [N, I], dskip [I] -> (y [S, I], h [N, I]). The channels
    of a time step are laid [I / 1024, 8, 128], a block of 1024 in one
    register, so that a step's work is whole registers and the 16 states of a
    block stay in registers over a chunk."""
    S, I = c.shape
    N = a.shape[0]
    blocks = I // BLOCK

    def tiles(x):       # [.., I] -> [.., blocks, 8, 128]
        return x.reshape(*x.shape[:-1], blocks, SUBLANES, LANES)

    by_time = pl.BlockSpec((chunk, None, SUBLANES, LANES),
                           lambda i, j: (j, i, 0, 0))
    scalars = pl.BlockSpec((chunk * N,), lambda i, j: (j,),
                           memory_space=pltpu.SMEM)
    y, h = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, states=N),
        grid=(blocks, S // chunk),
        in_specs=[scalars, scalars, by_time, by_time, by_time,
                  pl.BlockSpec((N, None, SUBLANES, LANES),
                               lambda i, j: (0, i, 0, 0)),
                  pl.BlockSpec((None, SUBLANES, LANES),
                               lambda i, j: (i, 0, 0))],
        out_specs=[by_time,
                   pl.BlockSpec((None, N, SUBLANES, LANES),
                                lambda i, j: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, blocks, SUBLANES, LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((blocks, N, SUBLANES, LANES),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(b.reshape(-1), cm.reshape(-1), tiles(c), tiles(d), tiles(z), tiles(a),
      tiles(dskip))
    return y.reshape(S, I), jnp.swapaxes(h, 0, 1).reshape(N, I)


@jax.named_scope("selective_scan")
def selective_scan(c, d, z, b, cm, a, dskip, length):
    """One prompt from a zero state: c, z [S, I]; d [S, I] float32; b, cm
    [S, N]; a [N, I]; dskip [I]; ``length`` of the S tokens are real.
    Returns (y [S, I] in c's dtype, the state [N, I] float32 after token
    ``length - 1``); y past ``length`` is the padding's and means nothing."""
    S, I = c.shape
    f32 = jnp.float32
    d = jnp.where(jnp.arange(S)[:, None] < length, d, 0.0)
    args = [x.astype(f32) for x in (c, d, z, b, cm, a, dskip)]
    chunk = kernel_chunk(S, I, jax.sharding.get_abstract_mesh().size)
    if chunk is not None:
        dispatch.note("selective_scan", "pallas")
        y, h = scan_kernel(*args, chunk=chunk)
    else:
        dispatch.note("selective_scan", "xla")
        y, h = _scan_xla(*args, chunk=min(XLA_CHUNK, S))
    return y.astype(c.dtype), h


@jax.named_scope("selective_scan")
def state_update(h, c, d, z, b, cm, a, dskip):
    """One token a slot: h [B, N, I] float32; c, z [B, I]; d [B, I] float32;
    b, cm [B, N] -> (y [B, I] in c's dtype, the new state [B, N, I])."""
    f32 = jnp.float32
    h, y = _step(h, c.astype(f32), d, z.astype(f32), b.astype(f32),
                 cm.astype(f32), a, dskip)
    return y.astype(c.dtype), h
