"""Operations and bytes a prefill of a decoder of state-space layers with an
attention layer among them needs, from shapes: ``tokens`` real prompt tokens
(no prefix store in this family: every prompt is prefilled whole). An
attention layer's token attends to every token before it; a mixer's scan is
nine operations an element ``(token, channel, state)``. The LM head runs at one
position. Bytes: every weight once, the attention layers' K and V rows and
each mixer's final state written. Padding to a bucket is the program's own
waste and is not counted.
"""

from __future__ import annotations

from benchmark.opcount import selective_scan
from benchmark.opcount.ssm_hybrid_decode_chunk import matmul_weights, shapes


def count(cfg: dict, tokens: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    per_token = matmul_weights(s) - s["H"] * s["V"]
    attention = 4.0 * s["n_attn"] * s["Q"] * tokens * (tokens + 1) / 2.0
    scan = s["n_mixer"] * selective_scan.count(
        tokens, s["I"], s["N"])["flops"]
    kv = 2 * s["n_attn"] * s["KV"] * kv_bytes * tokens
    return {"flops": 2.0 * per_token * tokens + attention + scan
            + 2.0 * s["H"] * s["V"],
            "bytes": wt_bytes * matmul_weights(s) + kv
            + s["n_mixer"] * s["state_bytes"]}
