"""Operations and bytes ONE decode step of a decoder of state-space layers
with an attention layer among them needs, from shapes: one new token for each
of ``active`` sequences, in a program that holds ``slots`` slots, whose
attention layers hold ``kv_rows`` live rows in all.

Bytes are what has to move across HBM once a step: every weight (bf16; the
tied embedding once, as the head), each mixer's state READ AND WRITTEN for
every slot the program touches (a state has no rows: it is the same bytes
whatever a slot's length, and the program's step runs over all its slots,
decoding or not), the LIVE rows of K and V of the attention layers, the new
rows written and the new tokens' embedding rows. Operations: two a weight and
active token, nine a state element and active slot, four a query width and
live row.
"""

from __future__ import annotations

from benchmark.opcount import selective_scan


def shapes(cfg: dict) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    i, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    layers = cfg["num_hidden_layers"]
    n_attn = sum(1 for x in range(layers)
                 if x % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return {
        "H": h, "Q": q, "KV": kv, "V": cfg["vocab_size"], "I": i, "N": n,
        "K": k, "L": layers, "n_attn": n_attn, "n_mixer": layers - n_attn,
        "mlp": 3 * h * f,
        "attn": h * (q + 2 * kv) + q * h,                   # wq wk wv wo
        # in_proj, x_proj, dt_proj, out_proj: what a token is multiplied by
        "mixer": h * 2 * i + i * (r + 2 * n) + r * i + i * h,
        # conv1d and its bias, dt's bias, A_log, D, the three small norms
        "mixer_small": i * k + i + i + i * n + i + r + 2 * n,
        # a slot's state of ONE mixer: the scan state in float32 and the
        # convolution's tail at the activations' width
        "state_bytes": 4 * i * n + 2 * i * (k - 1)}


def matmul_weights(s: dict) -> int:
    """Weights every token is multiplied by, the head's among them."""
    return (s["L"] * s["mlp"] + s["n_attn"] * s["attn"]
            + s["n_mixer"] * s["mixer"] + s["H"] * s["V"])


def count(cfg: dict, slots: float, active: float, kv_rows: float,
          wt_bytes: int = 2, kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    weights = wt_bytes * (matmul_weights(s) + 2 * s["L"] * s["H"] + s["H"]) \
        + 4 * s["n_mixer"] * s["mixer_small"]
    state = 2 * slots * s["n_mixer"] * s["state_bytes"]     # read and written
    kv_read = 2 * s["n_attn"] * s["KV"] * kv_bytes * kv_rows
    kv_write = 2 * s["n_attn"] * s["KV"] * kv_bytes * active
    embed = active * s["H"] * wt_bytes
    scan = s["n_mixer"] * selective_scan.count(
        active, s["I"], s["N"])["flops"]
    return {"bytes": weights + state + kv_read + kv_write + embed,
            "flops": 2.0 * matmul_weights(s) * active + scan
            + 4.0 * s["n_attn"] * s["Q"] * kv_rows}
