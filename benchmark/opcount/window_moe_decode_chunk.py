"""Operations and bytes ONE decode step of a window-and-full attention decoder
with an expert layer needs, from shapes: one new token for each of ``slots``
active sequences, whose window layers hold ``window_rows`` live ring rows in
all (the sum over slots of min(length, window)) and whose full layers hold
``full_rows``.

Bytes are what the algorithm has to move across HBM once a step: every bf16
weight of attention, the dense layers, the shared experts and the LM head,
the float32 routers, of the HELD expert stacks only the experts this step's
tokens can be expected to choose (each token takes ``top-k`` of the router's
experts; at even routing ``held * (1 - (1 - k / E) ** slots)`` distinct ones,
12.7 of 32 at 32 slots: a program that reads the whole stack reads more than
the step needs and shows a lower share), the LIVE rows of K and V by kind, the
new rows written and the new tokens' embedding rows. Operations: two a weight
and token for the dense parts, two a weight for the choices that fall on a
held expert (``slots * k * held / E`` a layer), four a query width and live
row in attention.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def shapes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    im, e = cfg["moe_intermediate_size"], cfg["router_experts"]
    types = cfg["layer_types"]
    n_dense = cfg["num_dense_layers"]
    return {
        "H": h, "Q": q, "KV": kv, "V": cfg["vocab_size"], "E": e,
        "K": cfg["num_experts_per_tok"], "held": cfg["experts_held"][1],
        "W": cfg["sliding_window"], "L": len(types), "n_dense": n_dense,
        "n_expert": len(types) - n_dense,
        "n_window": sum(1 for t in types if t == SLIDING),
        "n_full": sum(1 for t in types if t != SLIDING),
        "attn": h * (2 * q + 2 * kv) + q * h,       # wq, wg, wk, wv, wo
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "expert": 3 * h * im,                       # one expert; the shared one
        "router": h * e}


def whole_weights(s: dict) -> int:
    """bf16 weights every token passes through (no routed expert, no router)."""
    return (s["L"] * s["attn"] + s["n_dense"] * s["dense_mlp"]
            + s["n_expert"] * s["expert"] + s["H"] * s["V"])


def distinct_held(s: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens choose at even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** tokens)


def expert_layer(s: dict, tokens: float) -> dict:
    """One expert layer's routed and shared products and its router."""
    hits = tokens * s["K"] * s["held"] / s["E"]
    return {"bytes": 2 * s["expert"] * (1 + distinct_held(s, tokens))
            + 4 * s["router"],
            "flops": 2.0 * (s["expert"] * (tokens + hits)
                            + s["router"] * tokens)}


def count(cfg: dict, slots: float, window_rows: float, full_rows: float,
          wt_bytes: int = 2, kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    dense = whole_weights(s) - s["n_expert"] * s["expert"]
    layer = expert_layer(s, slots)
    rows = s["n_window"] * window_rows + s["n_full"] * full_rows
    kv_read = 2 * s["KV"] * kv_bytes * rows
    kv_write = 2 * s["KV"] * kv_bytes * s["L"] * slots
    embed = slots * s["H"] * wt_bytes
    return {"bytes": wt_bytes * dense + s["n_expert"] * layer["bytes"]
            + kv_read + kv_write + embed,
            "flops": 2.0 * dense * slots + s["n_expert"] * layer["flops"]
            + 4.0 * s["Q"] * rows}
