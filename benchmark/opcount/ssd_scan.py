"""Operations and bytes one call of the chunked state-space scan needs
(``kukeon_tpu/ops/ssd_scan.py``), from shapes: ``tokens`` time steps of one
sequence, ``heads`` heads of ``head_dim`` channels, ``states`` states a
channel shared by all heads (one group), chunks of ``chunk`` steps.

    y = ((C B^T) o L o dt) x + exp(cum) (C S_in) + D x;  S_out = ...

Operations are MATRIX operations, two a multiply-add, of the chunked form at
its published chunk: ``C B^T`` over the causal pairs of a chunk (once for all
heads), the ``[Q, Q]`` product with ``x`` over the causal pairs (a head), ``C
S_in`` and the chunk's input to the state (``2 x states x head_dim`` a token
and head each): 6.3 MFLOP a token at 128 heads of 64, 128 states and chunks of
256 (8.4 with the masked half of each ``[Q, Q]`` block counted). Forming the
decay matrix ``L`` is vector work (an exponential a causal pair and head) and
is not counted: a kernel bound by it reads a low share. Bytes are what the
algorithm has to move across HBM once: ``x`` in and ``y`` out (``tokens x
channels`` each, at the activations' width), ``B`` and ``C``, the step sizes
in float32, and the final state out in float32; never a ``tokens x heads x
head_dim x states`` array.
"""

from __future__ import annotations


def count(tokens: float, heads: int, head_dim: int, states: int,
          chunk: int = 256, act_bytes: int = 2) -> dict:
    channels = heads * head_dim
    pairs = (min(chunk, tokens) + 1) / 2.0      # causal keys a query, a chunk
    per_token = 2.0 * (states * pairs + channels * pairs
                       + 2 * states * channels)
    return {"flops": per_token * tokens,
            "bytes": act_bytes * (2 * tokens * channels + 2 * tokens * states)
            + 4 * tokens * heads + 4 * channels * states}
