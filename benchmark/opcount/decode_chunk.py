"""Operations and bytes ONE decode step of a dense GQA model needs, from
shapes: one new token for each of ``slots`` active sequences that together
hold ``kv_rows`` live rows of KV.

Bytes are what the algorithm has to move across HBM once per step: every int8
weight of the layers and the LM head with its float32 scales, the embedding
rows of the new tokens, the LIVE rows of K and V (what the program reads
beyond them is its own waste and shows as a lower share), and the new rows
written. Divided over ``chips`` for a tensor-parallel deployment.
"""

from __future__ import annotations


def shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"H": h, "I": i, "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "Q": q, "KV": kv, "D": d,
            "NH": cfg["num_attention_heads"],
            "layer_params": h * (q + 2 * kv) + q * h + 3 * h * i,
            "layer_channels": q + 2 * kv + h + 2 * i + h}


def count(cfg: dict, slots: float, kv_rows: float, chips: int = 1,
          act_bytes: int = 2, kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    weights = s["L"] * s["layer_params"] + s["H"] * s["V"]       # int8
    scales = 4 * (s["L"] * s["layer_channels"] + s["V"])
    kv_read = 2 * s["L"] * s["KV"] * kv_bytes * kv_rows
    kv_write = 2 * s["L"] * s["KV"] * kv_bytes * slots
    embed = slots * (s["H"] + 4)
    flops = 2.0 * weights * slots + 4.0 * s["L"] * s["Q"] * kv_rows
    return {"bytes": (weights + scales + kv_read + kv_write + embed) / chips,
            "flops": flops / chips}
