"""Operations and bytes ONE decode step of a latent attention that selects its
rows over an expert layer needs, from shapes: one new token for each of
``active`` sequences whose caches hold ``live_rows`` rows in all (a layer).

Bytes are what has to move across HBM once a step: the weights every token is
multiplied by (bf16; the router float32), the routed experts the step's tokens
HIT (a held expert nobody chose is not read: under even routing a token
chooses a given held expert with probability top-k / router width, so
``held x (1 - (1 - k/E) ** active)`` of them are read), the indexer's key of
every live row, the latent row of every SELECTED row (min(index_topk, length)
a slot: the others are never read), the new rows written, the new tokens'
embedding rows. Operations: two a weight and active token (an expert's for the
tokens that chose it), two a live row, index head and dim, and the absorbed
attention's two products over the selected rows.
"""

from __future__ import annotations


def shapes(cfg: dict) -> dict:
    h, q, r = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    im = cfg["moe_intermediate_size"]
    return {
        "H": h, "V": cfg["vocab_size"], "L": layers, "dense": dense,
        "NH": nh, "R": r, "Dr": dr, "Dn": dn, "Dv": dv, "Hi": hi, "Di": di,
        "topk": cfg["index_topk"], "K": cfg["num_experts_per_tok"],
        "E": cfg["router_experts"], "held": cfg["experts_held"][1],
        # wq_a, wq_b, wkv_a, wkv_b, wo
        "attn": h * q + q * nh * (dn + dr) + h * (r + dr)
        + r * nh * (dn + dv) + nh * dv * h,
        "indexer": q * hi * di + h * di + h * hi,
        "mlp": 3 * h * cfg["intermediate_size"], "expert": 3 * h * im,
        "router": h * cfg["router_experts"],
        "row": r + dr,                      # the latent row, as the model defines it
    }


def per_token_weights(s: dict) -> int:
    """Weights every token is multiplied by, but the routed experts' and the
    head's."""
    experts = s["L"] - s["dense"]
    return (s["L"] * (s["attn"] + s["indexer"]) + s["dense"] * s["mlp"]
            + experts * (s["expert"] + s["router"]))


def experts_read(s: dict, active: float) -> float:
    """Held experts a layer's step reads, under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** active)


def count(cfg: dict, active: float, live_rows: float,
          selected_rows: float | None = None, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    experts = s["L"] - s["dense"]
    if selected_rows is None:       # every slot alike
        each = live_rows / max(active, 1e-9)
        selected_rows = active * min(each, s["topk"])
    dense_w = per_token_weights(s) - experts * s["router"]
    weights = (wt_bytes * (dense_w + s["H"] * s["V"])
               + 4 * experts * s["router"]
               + wt_bytes * experts * experts_read(s, active) * s["expert"])
    cache = kv_bytes * s["L"] * (live_rows * s["Di"]
                                 + selected_rows * s["row"]
                                 + active * (s["Di"] + s["row"]))
    hit = active * s["K"] * s["held"] / s["E"]      # (token, choice) pairs here
    return {
        "bytes": weights + cache + active * s["H"] * wt_bytes,
        "flops": 2.0 * (per_token_weights(s) + s["H"] * s["V"]) * active
        + 2.0 * experts * s["expert"] * hit
        + s["L"] * (2.0 * s["Hi"] * s["Di"] * live_rows
                    + 2.0 * s["NH"] * selected_rows
                    * (s["R"] + s["Dr"] + s["R"]))}
