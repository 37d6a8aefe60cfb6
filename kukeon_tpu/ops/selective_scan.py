"""The selective scan of a Mamba-1 mixer: a recurrence over time whose decay
turns on the input.

    H_t = exp(d_t * A) * H_{t-1} + (d_t * c_t) * B_t        H [N, I], float32
    y_t = (sum_n H_t[n] * C_t[n] + D * c_t) * silu(z_t)

``c``, ``z`` are ``[.., I]`` (the convolved input and the gate), ``d`` ``[..,
I]`` float32 (the step size, already through its softplus), ``B``, ``C`` ``[..,
N]``, ``A`` ``[N, I]`` (negative), ``D`` ``[I]``. The state is held STATE-major,
``[N, I]`` with the channels on the lanes: an ``[I, 16]`` array would pad its
16 to a tile's 128 lanes and take eight times its bytes on a TPU.

Three entry points. ``selective_scan`` runs the ``S`` tokens of one prompt from a
zero state and returns every ``y`` and the state after token ``length - 1``
(past ``length`` the step size is masked to 0, so ``exp(0) = 1`` keeps the
state and the input term adds nothing: a prompt right-padded to its bucket
leaves the state of its last real token). It chooses its body from what the
call observes, as ``ops/attention.py`` does: on one TPU, with channels that
fill whole tiles, a Pallas kernel that walks time in chunks with the state of
1024 channels in registers, so that HBM sees ``c``, ``d``, ``z``, ``y`` once
and ``B``, ``C`` once a channel block and never an ``[S, I, N]`` array;
elsewhere a ``lax.scan`` over chunks of time steps that computes the same.
``state_update`` is one step for ``B`` slots: plain ``jax.numpy`` that XLA
fuses into one pass over a layer's state. ``update_held`` is a decode step's:
one step in the HELD stack ``[mixers, B, N, I]`` for the slots that decode.
On one TPU a second Pallas kernel walks the ACTIVE slots only, each slot's
``[N, I]`` copied in, replaced and copied back where it lay, so that an idle
slot moves no byte; elsewhere ``state_update`` over the layer's slice with
the idle slots' old state selected back in the write.

``state_update`` and ``update_held`` also take ``A`` as ``[1, I]``: a decay
that does not turn on the state's index (a Mamba-2 mixer's, one scalar a head,
repeated over the head's channels), whose exponential is then one row and not
``N`` of them. The prefill of such a mixer is ``ops/ssd_scan.py``'s.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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


# --- A decode step's update of the HELD stack --------------------------------

UPDATE_LANES = 512  # channels of a slot's state the kernel computes at once
UPDATE_TILE = 32768     # ... and at most this many state elements (16 states:
                        # 512 channels; 128 states: 256, since a dynamic row
                        # of 128 lanes does not lower), or registers spill
UPDATE_DEPTH = 4    # buffers of one slot's state [N, I]: one computed, two on
                    # their way in, one on its way out (three cost a walk 40%
                    # more time, six or eight gain nothing: PERF.md, PR 41)


def update_kernel_runs(channels: int, states: int, devices: int) -> bool:
    """Whether a decode step's update is the Pallas body (else
    ``state_update`` on the layer's slice): a TPU, one device, channels in
    whole blocks as the prefill's kernel asks, states in whole sublanes."""
    return (jax.default_backend() == "tpu" and devices == 1
            and channels % BLOCK == 0 and states % SUBLANES == 0)


class Walk(NamedTuple):
    """The slots a decode step updates, made once a step (``live_slots``)
    and read by every mixer of it."""
    active: jax.Array   # [B] bool
    slots: jax.Array    # [B] int32: the active slots' numbers, ascending,
                        # then zeros
    live: jax.Array     # [1] int32: how many they are


def live_slots(active) -> Walk:
    slots = jnp.nonzero(active, size=active.shape[0], fill_value=0)[0]
    return Walk(active, slots.astype(jnp.int32),
                jnp.sum(active, dtype=jnp.int32).reshape(1))


def _update_kernel(layer_ref, slots_ref, live_ref, c_ref, d_ref, z_ref,
                   bt_ref, cmt_ref, a_ref, dskip_ref, _held, y_ref, h_hbm,
                   buf, sem, *, depth, lanes):
    """No grid: ONE loop over the ``live_ref[0]`` active slots, in scalars.
    c, d, z, y [B, I] and a [N, I] (or [1, I]), dskip [1, I] in VMEM; bt,
    cmt [N, B] (a slot's B and C as a column over the states); ``h_hbm`` the
    held stack [mixers, B, N, I] in HBM, the output that IS the operand
    ``_held``. A slot's state comes into ``buf[t % depth]``, is replaced there
    and goes back to where it came from, with the next two slots' on their
    way in and the last one's on its way out."""
    layer, live = layer_ref[0], live_ref[0]
    channels = y_ref.shape[1]

    def into(t):        # HBM -> buf
        return pltpu.make_async_copy(h_hbm.at[layer, slots_ref[t]],
                                     buf.at[t % depth], sem.at[0, t % depth])

    def back(t):        # buf -> HBM
        return pltpu.make_async_copy(buf.at[t % depth],
                                     h_hbm.at[layer, slots_ref[t]],
                                     sem.at[1, t % depth])

    for t in range(depth - 2):
        @pl.when(t < live)
        def _first():
            into(t).start()

    # a slot that is not on the walk: nothing undefined may leave the call
    y_ref[...] = jnp.zeros_like(y_ref)

    def step(t, _):
        slot, k = slots_ref[t], t % depth
        into(t).wait()

        @pl.when(t >= 2)    # buf[(t - 2) % depth] is the next to fill
        def _written():
            back(t - 2).wait()

        @pl.when(t + depth - 2 < live)
        def _next():
            into(t + depth - 2).start()

        row = pl.ds(slot, 1)
        mine = jax.lax.broadcasted_iota(jnp.int32, bt_ref.shape, 1) == slot
        b = jnp.sum(jnp.where(mine, bt_ref[...], 0.0), axis=1, keepdims=True)
        cm = jnp.sum(jnp.where(mine, cmt_ref[...], 0.0), axis=1,
                     keepdims=True)                                 # [N, 1]
        for j in range(channels // lanes):
            at = pl.ds(j * lanes, lanes)
            c, d, z = c_ref[row, at], d_ref[row, at], z_ref[row, at]
            h = jnp.exp(d * a_ref[:, at]) * buf[k, :, at] + (d * c) * b
            buf[k, :, at] = h
            y = jnp.sum(h * cm, axis=0, keepdims=True) + dskip_ref[:, at] * c
            y_ref[row, at] = _gate(y, z)
        back(t).start()
        return 0

    jax.lax.fori_loop(0, live, step, 0)
    for t in (live - 2, live - 1):
        @pl.when(t >= 0)
        def _last():
            back(t).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def update_kernel(held, layer, slots, live, c, d, z, b, cm, a, dskip, *,
                  interpret: bool = False):
    """The Pallas body of ``update_held``: float32 operands; ``held`` the
    stack [mixers, B, N, I], which stays in HBM whole (a layer sliced out of
    it would be copied on its way into the call) and is aliased to the
    result, so a scan that carries it still carries one array; ``layer`` its
    mixer (traced); ``slots``, ``live`` a ``Walk``'s; ``a`` [N, I] or [1, I].
    Returns (y [B, I] float32, zero where a slot is not on the walk; the
    stack). Jitted, so the two runs of mixers of a decode program lower it
    once (``ops/decode_attention.py`` says what a call site costs a boot)."""
    _, B, N, I = held.shape
    lanes = min(I, max(2 * LANES, min(UPDATE_LANES, UPDATE_TILE // N)))
    f32 = jnp.float32
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_vmem = 4 * (4 * B * I + (a.shape[0] + UPDATE_DEPTH * N) * I + I
                   + 2 * N * max(B, LANES))
    return pl.pallas_call(
        functools.partial(_update_kernel, depth=UPDATE_DEPTH, lanes=lanes),
        out_shape=[jax.ShapeDtypeStruct((B, I), f32),
                   jax.ShapeDtypeStruct(held.shape, held.dtype)],
        in_specs=[smem] * 3 + [vmem] * 7 + [hbm],
        out_specs=[vmem, hbm],
        scratch_shapes=[pltpu.VMEM((UPDATE_DEPTH, N, I), f32),
                        pltpu.SemaphoreType.DMA((2, UPDATE_DEPTH))],
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(in_vmem * 1.5) + (4 << 20)),
        name="ssm_state_update",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, live, c, d, z,
      b.T, cm.T, a, dskip.reshape(1, I), held)


@jax.named_scope("selective_scan")
def update_held(held, layer, walk: Walk, c, d, z, b, cm, a, dskip):
    """One token a slot, in the HELD stack: ``held`` [mixers, B, N, I]
    float32, of which mixer ``layer``'s state moves one step where a slot is
    active and stays, bit for bit, where it is not; ``walk`` is
    ``live_slots(active)``, made once a step and not once a mixer; c, z [B,
    I]; d [B, I] float32; b, cm [B, N]. Returns (y [B, I] in c's dtype, the
    stack). On one TPU a kernel walks the active slots only, at every
    occupancy: an idle slot moves no byte (its y is zero), and with every slot
    active the walk still moves the state once where XLA passes over it twice
    (the update, then the sum over the states for y: PERF.md section 5,
    PR 41). Elsewhere ``state_update`` computes every slot of the layer's
    slice and an idle slot's old state rides back in the write (its y is its
    own, dropped downstream like its token)."""
    f32 = jnp.float32
    if update_kernel_runs(held.shape[3], held.shape[2],
                          jax.sharding.get_abstract_mesh().size):
        dispatch.note("state_update", "pallas")
        y, held = update_kernel(
            held, layer, walk.slots, walk.live, c.astype(f32), d,
            z.astype(f32), b.astype(f32), cm.astype(f32), a, dskip)
        return y.astype(c.dtype), held
    dispatch.note("state_update", "xla")
    h = jax.lax.dynamic_index_in_dim(held, layer, keepdims=False)
    y, new = state_update(h, c, d, z, b, cm, a, dskip)
    # the select rides in the update's own pass: no second array of the
    # stack's size is made
    new = jnp.where(walk.active[:, None, None], new, h)
    return y, jax.lax.dynamic_update_index_in_dim(held, new, layer, 0)
