"""Operations and bytes a prefill of a latent attention that selects its rows
over an expert layer needs, from shapes: ``tokens`` real prompt tokens (no
prefix store in this family: every prompt is prefilled whole). A token's index
scores run over every token at or before it; its attention, in the expanded
form, over the min(index_topk, position + 1) it selects. Of the routed experts
a token is multiplied by those it chose among the held (top-k x held / router
width under even routing). The LM head runs at one position. Bytes: every
weight once and the cached rows written. Padding to a bucket and the pairs a
mask throws away are the program's own waste and are not counted.
"""

from __future__ import annotations

from benchmark.opcount.sparse_latent_decode_chunk import (per_token_weights,
                                                          shapes)
from benchmark.opcount.sparse_masked_attention import selected_pairs


def count(cfg: dict, tokens: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    experts = s["L"] - s["dense"]
    hit = s["K"] * s["held"] / s["E"]
    index = 2.0 * s["Hi"] * s["Di"] * tokens * (tokens + 1) / 2.0
    attend = (2.0 * s["NH"] * (s["Dn"] + s["Dr"] + s["Dv"])
              * selected_pairs(0, tokens, s["topk"]))
    return {
        "flops": 2.0 * (per_token_weights(s)
                        + experts * s["expert"] * hit) * tokens
        + s["L"] * (index + attend) + 2.0 * s["H"] * s["V"],
        "bytes": wt_bytes * (per_token_weights(s) + s["H"] * s["V"]
                             + experts * s["held"] * s["expert"])
        + kv_bytes * s["L"] * tokens * (s["row"] + s["Di"])}
