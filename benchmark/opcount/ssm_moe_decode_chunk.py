"""Operations and bytes ONE decode step of a decoder of Mamba-2 mixers with an
attention layer among them and an expert layer in every layer needs, from
shapes: one new token for each of ``active`` sequences, whose attention layers
hold ``kv_rows`` live rows in all.

Bytes are what has to move across HBM once a step: every weight outside the
routed experts (bf16: mixers, attention, shared experts, the tied embedding
once, as the head; the float32 routers and the scan's small leaves), of the
HELD expert stacks only the experts this step's tokens can be expected to
choose (each token takes ``top-k`` of the router's experts; at even routing
``held * (1 - (1 - k / E) ** active)`` distinct ones a layer, 33.9 of 36 at 20
tokens), each mixer's state READ AND WRITTEN for the ACTIVE slots only (a
state has no rows: the same bytes whatever a slot's length; the program's step
touches no idle slot's, so none is counted: counting every held slot would
flatter the step by the idle ones), the LIVE rows of K and V, the new rows
written and the new tokens' embedding rows. Operations: two a weight and
active token for the dense parts, two a weight for the choices that fall on a
held expert (``active * k * held / E`` a layer), seven a state element and
active slot, four a query width and live row.
"""

from __future__ import annotations

STATE_OPS = 7   # decay x state, + input term (two), x C, sum; exp is a head's


def shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    mh, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"])
    i, k = mh * p, cfg["mamba_d_conv"]
    c = i + 2 * cfg["mamba_n_groups"] * n
    types = cfg["layer_types"]
    n_attn = sum(1 for t in types if t == "attention")
    return {
        "H": h, "Q": q, "KV": kv, "V": cfg["vocab_size"], "I": i, "N": n,
        "MH": mh, "P": p, "C": c, "L": len(types), "n_attn": n_attn,
        "n_mixer": len(types) - n_attn, "E": cfg["router_experts"],
        "K": cfg["num_experts_per_tok"], "held": cfg["experts_held"][1],
        "chunk": cfg["mamba_chunk_size"],
        "attn": h * (q + 2 * kv) + q * h,                   # wq wk wv wo
        # in_proj (z | x B C | dt) and out_proj
        "mixer": h * (i + c + mh) + i * h,
        # conv1d and its bias, the gated norm's gain (bf16); dt's bias, A, D
        "mixer_small_bf16": c * k + c + i, "mixer_small_f32": 3 * mh,
        "expert": 3 * h * cfg["intermediate_size"],
        "shared": 3 * h * cfg["shared_intermediate_size"],
        "router": h * cfg["router_experts"],
        # a slot's state of ONE mixer: the scan state in float32 and the
        # convolution's tail at the activations' width
        "scan_state": 4 * i * n, "tail": 2 * c * (k - 1)}


def dense_weights(s: dict) -> int:
    """bf16 weights every token is multiplied by (no routed expert, no
    router), the head's among them."""
    return (s["n_mixer"] * s["mixer"] + s["n_attn"] * s["attn"]
            + s["L"] * s["shared"] + s["H"] * s["V"])


def distinct_held(s: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens choose at even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** tokens)


def routed(s: dict, tokens: float) -> dict:
    """One layer's routed products and its router."""
    hits = tokens * s["K"] * s["held"] / s["E"]
    return {"bytes": 2 * s["expert"] * distinct_held(s, tokens)
            + 4 * s["router"],
            "flops": 2.0 * (s["expert"] * hits + s["router"] * tokens)}


def count(cfg: dict, active: float, kv_rows: float, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    small = s["n_mixer"] * (wt_bytes * s["mixer_small_bf16"]
                            + 4 * s["mixer_small_f32"]) \
        + wt_bytes * (2 * s["L"] + 1) * s["H"]              # the norms
    layer = routed(s, active)
    state = 2 * active * s["n_mixer"] * (s["scan_state"] + s["tail"])
    kv_read = 2 * s["n_attn"] * s["KV"] * kv_bytes * kv_rows
    kv_write = 2 * s["n_attn"] * s["KV"] * kv_bytes * active
    embed = active * s["H"] * wt_bytes
    return {"bytes": wt_bytes * dense_weights(s) + small
            + s["L"] * layer["bytes"] + state + kv_read + kv_write + embed,
            "flops": 2.0 * dense_weights(s) * active + s["L"] * layer["flops"]
            + STATE_OPS * active * s["n_mixer"] * s["I"] * s["N"]
            + 4.0 * s["n_attn"] * s["Q"] * kv_rows}
