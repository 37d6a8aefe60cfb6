"""Device-mesh construction.

Canonical axis names for the whole framework (the scaling-book convention):

  - ``data``:    pure data parallelism (gradients all-reduced).
  - ``fsdp``:    data parallelism with sharded params/optimizer state
                 (params all-gathered per layer, grads reduce-scattered).
  - ``tensor``:  tensor (megatron-style) parallelism inside a layer.
  - ``seq``:     sequence/context parallelism (ring attention).
  - ``expert``:  expert parallelism (MoE: experts sharded over chips, token
                 dispatch/combine become all-to-alls inserted by GSPMD from
                 the einsum shardings — models/moe.py).
  - ``pipe``:    pipeline parallelism (layer stages over chips; GPipe
                 microbatch schedule with ppermute activation transfer —
                 parallel/pipeline.py). Outermost axis: stage hops are the
                 lowest-frequency, most latency-tolerant traffic, so they
                 map to the outer interconnect dimension (DCN on multi-host).

Serving uses (data, tensor); training adds fsdp/seq; MoE models add expert.
On a TPU slice the mesh should be laid out so that ``tensor`` (highest-
bandwidth collectives) maps to the innermost ICI dimension —
``jax.make_mesh`` handles device ordering.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipe"


def make_mesh(
    data: int = 1,
    fsdp: int = 1,
    tensor: int = 1,
    seq: int = 1,
    expert: int = 1,
    pipe: int = 1,
    *,
    devices=None,
) -> Mesh:
    """Build a mesh with the canonical axes; sizes must multiply to #devices."""
    devices = devices if devices is not None else jax.devices()
    want = data * fsdp * tensor * seq * expert * pipe
    if want != len(devices):
        raise ValueError(
            f"mesh {pipe}x{data}x{fsdp}x{expert}x{seq}x{tensor}={want} != "
            f"{len(devices)} devices"
        )
    # Auto axis types: GSPMD propagates shardings from the annotations we set
    # at jit boundaries (jax 0.9 defaults to Explicit mode, which turns
    # with_sharding_constraint into an assert — not what this codebase wants).
    return jax.make_mesh(
        (pipe, data, fsdp, expert, seq, tensor),
        (AXIS_PIPE, AXIS_DATA, AXIS_FSDP, AXIS_EXPERT, AXIS_SEQ, AXIS_TENSOR),
        devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * 6,
    )


def serving_mesh(n_devices: int | None = None) -> Mesh:
    """All chips on ``tensor`` — the latency-optimal layout for one model.

    ``n_devices`` is a hard request, not a hint: asking for more chips than
    the process can see fails loudly here (a ``chips: N`` grant that cannot
    be honored must die at boot, never silently serve on fewer chips)."""
    visible = len(jax.devices())
    n = n_devices if n_devices is not None else visible
    if n < 1:
        raise ValueError(f"serving mesh needs >= 1 device, got {n}")
    if n > visible:
        raise ValueError(
            f"serving mesh wants {n} chips but only {visible} visible "
            "(check the cell's chip grant / TPU_VISIBLE_DEVICES)")
    return make_mesh(tensor=n, devices=jax.devices()[:n])


def training_mesh(n_devices: int | None = None, tensor: int = 1, seq: int = 1) -> Mesh:
    """FSDP over whatever is left after tensor/seq axes."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n % (tensor * seq):
        raise ValueError(f"{n} devices not divisible by tensor*seq={tensor * seq}")
    return make_mesh(fsdp=n // (tensor * seq), tensor=tensor, seq=seq,
                     devices=jax.devices()[:n])


def largest_pow2_leq(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n > 0 else 1


def auto_mesh_shape(n_devices: int) -> dict[str, int]:
    """Heuristic serving layout: tensor up to 8 (one ICI ring), data beyond.

    ``data * tensor == n_devices`` always — a non-power-of-two count picks
    its largest divisor <= 8 for the tensor axis (6 chips -> tensor=6,
    12 -> tensor=6 x data=2) instead of truncating to a power of two and
    dropping chips. A prime count degenerates to tensor=n_devices, which
    is still every chip; callers that need a specific slice size say so
    via :func:`serving_mesh` and get a loud error instead."""
    if n_devices < 1:
        raise ValueError(f"auto_mesh_shape needs >= 1 device, got {n_devices}")
    tensor = max(d for d in range(1, min(8, n_devices) + 1)
                 if n_devices % d == 0)
    return {"data": n_devices // tensor, "tensor": tensor}
