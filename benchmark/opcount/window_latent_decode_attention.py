"""Operations and bytes ONE call of the window layers' decode attention needs
(``window_latent_decode_attention`` of ``ops/sparse_attention.py``): ``slots``
active slots whose rings hold ``ring_rows`` rows inside their windows in all,
``heads`` absorbed queries a slot of ``width`` values (the latent row as the
model defines it: ``kv_lora_rank + qk_rope_head_dim``) of which the first
``value`` are mixed. Bytes: every attended ring row once, the queries in and
the mixes out; a row behind the window, a row of an idle slot and the lanes
the chip's tiling adds to a row are not needed. Operations: the two products
over the attended rows and the step's own.
"""

from __future__ import annotations


def count(slots: float, ring_rows: float, heads: int, width: int, value: int,
          kv_bytes: int = 2) -> dict:
    rows = ring_rows + slots            # each slot's own row takes part
    return {"bytes": kv_bytes * (ring_rows * width
                                 + slots * heads * (width + value)
                                 + slots * width),
            "flops": 2.0 * heads * rows * (width + value)}
