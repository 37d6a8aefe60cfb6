"""Operations and bytes a prefill of a window-and-full attention decoder with
an expert layer needs, from shapes: ``tokens`` real prompt tokens (no prefix
store in this family: every prompt is prefilled whole). A full layer's token
attends to every token before it; a window layer's to the band of
``sliding_window`` behind it. The LM head runs at one position; the held
expert stacks are read once (a prompt's tokens reach every held expert); of
the routed choices the share ``held / E`` falls on this chip. Padding to a
bucket is the program's own waste and is not counted.
"""

from __future__ import annotations

from benchmark.opcount.window_moe_decode_chunk import expert_layer, shapes


def pairs(tokens: float, window: float | None = None) -> float:
    """(query, key) pairs of causal attention, a window layer's band."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * window


def count(cfg: dict, tokens: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    per_token = s["L"] * s["attn"] + s["n_dense"] * s["dense_mlp"]
    layer = expert_layer(s, tokens)
    attention = 4.0 * s["Q"] * (s["n_full"] * pairs(tokens)
                                + s["n_window"] * pairs(tokens, s["W"]))
    kv = 2 * s["L"] * s["KV"] * kv_bytes * tokens
    return {"flops": 2.0 * per_token * tokens + s["n_expert"] * layer["flops"]
            + attention + 2.0 * s["H"] * s["V"],
            "bytes": wt_bytes * (per_token + s["H"] * s["V"])
            + s["n_expert"] * layer["bytes"] + kv}
