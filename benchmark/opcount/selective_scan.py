"""Operations and bytes one call of the selective scan needs
(``kukeon_tpu/ops/selective_scan.py``), from shapes: ``tokens`` time steps of
one sequence, ``channels`` channels, ``states`` states a channel.

    H_t = exp(d_t * A) * H_{t-1} + (d_t * c_t) * B_t;  y_t = H_t C_t + D c_t

Bytes are what the algorithm has to move across HBM once: ``c``, ``d``, ``z``
in and ``y`` out (``tokens x channels`` each, at the activations' width), ``B``
and ``C`` (``tokens x states``), and the final state out in float32; never a
``tokens x channels x states`` array. Operations: nine an element ``(t,
channel, state)`` (the product in the exponent, the exponential, two products
and a sum for the state, two products and a sum for its input and output; the
gate and the skip are a ``states``-th of that and are left out). They are
vector operations: against the chip's bf16 MATRIX peak they bound nothing, the
bytes do, and a kernel bound by its vector arithmetic reads a low share.
"""

from __future__ import annotations

OPS_PER_ELEMENT = 9


def count(tokens: float, channels: int, states: int,
          act_bytes: int = 2) -> dict:
    return {"bytes": act_bytes * (4 * tokens * channels + 2 * tokens * states)
            + 4 * channels * states,
            "flops": float(OPS_PER_ELEMENT) * tokens * channels * states}
