"""Operations and bytes a prefill of a dense GQA model needs, from shapes:
``new_tokens`` real prompt tokens attending causally to themselves and to
``cached_tokens`` rows already held (0 without a prefix hit); the LM head runs
at one position. Padding to a bucket is the program's own waste and is not
counted. Divided over ``chips`` for a tensor-parallel deployment.
"""

from __future__ import annotations

from benchmark.opcount.decode_chunk import shapes


def count(cfg: dict, new_tokens: float, cached_tokens: float = 0.0,
          chips: int = 1, kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    layer_weights = s["L"] * s["layer_params"]
    pairs = new_tokens * cached_tokens + new_tokens * (new_tokens + 1) / 2.0
    flops = (2.0 * layer_weights * new_tokens + 4.0 * s["L"] * s["Q"] * pairs
             + 2.0 * s["H"] * s["V"])
    kv = 2 * s["L"] * s["KV"] * kv_bytes * (cached_tokens + new_tokens)
    bytes_ = layer_weights + s["H"] * s["V"] + kv
    return {"flops": flops / chips, "bytes": bytes_ / chips}
