"""Weights-only int8 matmul Pallas kernel (decode fast path).

TPU decode is HBM-bound: every weight byte streams through HBM once per
step, so int8 weights halve step time *if* int8 is what actually crosses
HBM. XLA's ``astype``-dequant materializes a full bf16 copy (and the
s8->bf16 relayout is slow), so the win never lands; this kernel reads the
int8 block into VMEM, dequantizes in-register on the VPU, and feeds the
MXU — HBM traffic is the int8 bytes plus activations.

Shapes: ``h [B, K] @ q [K, N] * s [N] -> [B, N]`` (or ``q [N, K]`` with
``transpose=True`` for tied-embedding LM heads). B is the decode batch
(a few slots), padded to the bf16 sublane tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kukeon_tpu.ops import dispatch

# VMEM budget for one weight block (~half of the ~16 MB/core VMEM stays
# free for h/out/accumulators and double buffering).
_BLOCK_BYTES = 4 * 1024 * 1024
_MIN_TILE = 256


def _tile_n(k: int, n: int) -> int:
    t = max(_MIN_TILE, min(2048, _BLOCK_BYTES // max(k, 1)))
    t = min(t, n)
    # Lane dim must stay a multiple of 128; shrink to divide n evenly.
    t = max(128, (t // 128) * 128)
    while n % t:
        t -= 128
    return max(t, 128)


def _kernel(h_ref, q_ref, s_ref, o_ref):
    w = q_ref[:].astype(jnp.bfloat16)           # dequant in VMEM (VPU)
    acc = jnp.dot(h_ref[:], w, preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _kernel_t(h_ref, q_ref, s_ref, o_ref):
    w = q_ref[:].astype(jnp.bfloat16)           # [T, K] block
    acc = jax.lax.dot_general(
        h_ref[:], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transpose",))
def int8_matmul(h: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                *, transpose: bool = False) -> jnp.ndarray:
    """h [B, K] bf16 @ int8 weights, dequantized on-chip.

    ``transpose=False``: q [K, N], s [N] -> out [B, N]
    ``transpose=True``:  q [N, K], s [N] -> out [B, N]
    """
    B, K = h.shape
    N = q.shape[0] if transpose else q.shape[1]
    if (K % 128) or (N % 128) or B > 64 or jax.default_backend() != "tpu":
        # Odd shapes (tests, tiny models), prefill-sized batches (the [Bp, K]
        # activation block must stay far under VMEM; prefill is MXU-bound so
        # XLA's dequant-fused dot is the right tool there), and non-TPU
        # backends: plain XLA fallback.
        dispatch.note("int8_matmul", "xla")
        w = q.astype(h.dtype)
        out = jax.lax.dot_general(
            h, w, (((1,), (1 if transpose else 0,)), ((), ())))
        return out * s.astype(h.dtype)

    dispatch.note("int8_matmul", "pallas")
    # Pad B up to the bf16 sublane tile so the MXU operand is well-formed.
    Bp = max(16, ((B + 15) // 16) * 16)
    if Bp != B:
        h = jnp.pad(h, ((0, Bp - B), (0, 0)))

    T = _tile_n(K, N)
    grid = (N // T,)
    s2 = s.reshape(1, N)
    if transpose:
        kernel, q_spec = _kernel_t, pl.BlockSpec((T, K), lambda j: (j, 0))
    else:
        kernel, q_spec = _kernel, pl.BlockSpec((K, T), lambda j: (0, j))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((Bp, N), h.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bp, K), lambda j: (0, 0)),
            q_spec,
            pl.BlockSpec((1, T), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((Bp, T), lambda j: (0, j)),
    )(h, q, s2)
    return out[:B] if Bp != B else out


def int8_matmul_expert(x: jnp.ndarray, q: jnp.ndarray,
                       s: jnp.ndarray) -> jnp.ndarray:
    """Per-expert batched int8 matmul: x [E, C, K] @ q [E, K, N] * s [E, N]
    -> [E, C, N] (the MoE decode expert stacks: w_gate/w_up/w_down).

    On TPU at decode-sized C the E expert blocks run through the Pallas
    kernel one expert at a time (E is small and static, so this is a fixed
    unroll, and each weight block streams HBM as int8); everywhere else —
    CPU, odd shapes, prefill-sized C — the XLA dequant-fused einsum is the
    right tool and the fallback.
    """
    E, C, K = x.shape
    N = q.shape[-1]
    if (K % 128) or (N % 128) or C > 64 or jax.default_backend() != "tpu":
        dispatch.note("int8_matmul_expert", "xla")
        raw = jnp.einsum("eck,ekn->ecn", x, q.astype(x.dtype))
        return raw * s[:, None, :].astype(x.dtype)
    dispatch.note("int8_matmul_expert", "pallas")
    return jnp.stack([int8_matmul(x[e], q[e], s[e]) for e in range(E)])
