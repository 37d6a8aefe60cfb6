"""Operations and bytes a prefill of a decoder of Mamba-2 mixers with an
attention layer among them and an expert layer in every layer needs, from
shapes: ``tokens`` real prompt tokens (no prefix store in this family: every
prompt is prefilled whole). An attention layer's token attends to every token
before it; a mixer's scan is the chunked matrix form
(``opcount/ssd_scan.py``); of the routed choices the share ``held / E`` falls
on this chip and the held expert stacks are read as far as the prompt's
tokens reach them (all of them from a few dozen tokens on). The LM head runs
at one position. Bytes: every weight once, the attention layers' K and V rows
and each mixer's final state and tail written. Padding to a bucket is the
program's own waste and is not counted.
"""

from __future__ import annotations

from benchmark.opcount import ssd_scan
from benchmark.opcount.ssm_moe_decode_chunk import (dense_weights, routed,
                                                    shapes)


def count(cfg: dict, tokens: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    per_token = dense_weights(s) - s["H"] * s["V"]
    layer = routed(s, tokens)
    attention = 4.0 * s["n_attn"] * s["Q"] * tokens * (tokens + 1) / 2.0
    scan = s["n_mixer"] * ssd_scan.count(
        tokens, s["MH"], s["P"], s["N"], s["chunk"])["flops"]
    kv = 2 * s["n_attn"] * s["KV"] * kv_bytes * tokens
    return {"flops": 2.0 * per_token * tokens + s["L"] * layer["flops"]
            + attention + scan + 2.0 * s["H"] * s["V"],
            "bytes": wt_bytes * dense_weights(s) + s["L"] * layer["bytes"]
            + kv + s["n_mixer"] * (s["scan_state"] + s["tail"])}
