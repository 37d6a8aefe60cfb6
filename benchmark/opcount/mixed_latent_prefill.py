"""Operations and bytes a prefill of the two-kind latent decoder needs, from
shapes: ``tokens`` real prompt tokens (no prefix store in this family: every
prompt is prefilled whole). In a full layer a token's index scores run over
every token at or before it and its attention, in the expanded form, over the
min(index_topk, position + 1) it selects; in a window layer its attention runs
over the min(sliding_window_size, position + 1) positions of its window. Of
the routed experts a token is multiplied by those it chose among the held
(top-k x held / router width under even routing). The LM head runs at one
position. Bytes: every weight once (a prompt's tokens reach every held expert)
and the cached rows written, each as wide as the model defines it. Padding to
a bucket, the pairs a mask throws away and a band's corners are the program's
own waste and are not counted.
"""

from __future__ import annotations

from benchmark.opcount.mixed_latent_decode_chunk import (per_token_weights,
                                                         shapes)
from benchmark.opcount.sparse_masked_attention import selected_pairs


def count(cfg: dict, tokens: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    f, w = s["full"], s["sliding"]
    experts = s["L"] - s["dense"]
    hit = s["K"] * s["held"] / s["E"]
    index = 2.0 * s["Hi"] * s["Di"] * tokens * (tokens + 1) / 2.0
    attend = (2.0 * f["NH"] * (f["Dn"] + f["Dr"] + f["Dv"])
              * selected_pairs(0, tokens, s["topk"]))
    band = (2.0 * w["NH"] * (w["Dn"] + w["Dr"] + w["Dv"])
            * selected_pairs(0, tokens, s["behind"] + 1))
    return {
        "flops": 2.0 * (per_token_weights(s)
                        + experts * s["expert"] * hit) * tokens
        + s["fulls"] * (index + attend) + s["windows"] * band
        + 2.0 * s["H"] * s["V"],
        "bytes": wt_bytes * (per_token_weights(s) + s["H"] * s["V"]
                             + experts * s["held"] * s["expert"])
        + kv_bytes * tokens * (s["fulls"] * (f["row"] + s["Di"])
                               + s["windows"] * w["row"])}
