"""Operations and bytes ONE decode step of a decoder NEEDS whose layers are of
two kinds, each with a latent attention of its own (selecting full layers, and
window layers over a ring), over an expert layer, from shapes: one new token
for each of ``active`` sequences whose caches hold ``live_rows`` tokens in all.

What it needs, not what the chip holds. Bytes across HBM once a step: the
weights every token is multiplied by (bf16; the router float32), the routed
experts the step's tokens REACH (``experts_reached`` a layer, as the expert
layer's tally counts them; without it what even routing gives ``active``
tokens: ``held x (1 - (1 - k/E) ** active)``), of a full layer the index key
of every live row and the latent row of every SELECTED one (min(index_topk,
length) a slot), of a window layer the ring rows inside the window
(min(sliding_window_size - 1, length) a slot) and never a row behind it, the
new rows written, the new tokens' embedding rows. A row is as wide as the
model defines it (576 and 1088 values), not as the chip's tiling holds it.
Operations: two a weight and active token (an expert's for the tokens that
chose it), two a live row, index head and dim, and each absorbed attention's
two products over the rows it attends.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def _attention(cfg: dict, pre: str) -> dict:
    h, q, r = cfg["hidden_size"], cfg[pre + "q_lora_rank"], \
        cfg[pre + "kv_lora_rank"]
    nh = cfg[pre + "num_attention_heads"]
    dn, dr, dv = (cfg[pre + "qk_nope_head_dim"], cfg[pre + "qk_rope_head_dim"],
                  cfg[pre + "v_head_dim"])
    return {"NH": nh, "R": r, "Dr": dr, "Dn": dn, "Dv": dv, "row": r + dr,
            # wq_a, wq_b, wkv_a, wkv_b, wo, the gate a head
            "weights": h * q + q * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h + h * nh}


def shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    window = sum(t == SLIDING for t in cfg["layer_types"])
    return {
        "H": h, "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"], "windows": window,
        "fulls": cfg["num_hidden_layers"] - window,
        "full": _attention(cfg, ""), "sliding": _attention(cfg, "swa_"),
        "behind": cfg["sliding_window_size"] - 1,
        "Hi": hi, "Di": di, "topk": cfg["index_topk"],
        "K": cfg["num_experts_per_tok"], "E": cfg["router_experts"],
        "held": cfg["experts_held"][1],
        "indexer": cfg["q_lora_rank"] * hi * di + h * di + h * hi,
        "mlp": 3 * h * cfg["intermediate_size"],
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "router": h * cfg["router_experts"],
    }


def per_token_weights(s: dict) -> int:
    """Weights every token is multiplied by, but the routed experts' and the
    head's (the shared expert is one expert's size)."""
    experts = s["L"] - s["dense"]
    return (s["fulls"] * (s["full"]["weights"] + s["indexer"])
            + s["windows"] * s["sliding"]["weights"] + s["dense"] * s["mlp"]
            + experts * (s["expert"] + s["router"]))


def experts_read(s: dict, active: float) -> float:
    """Held experts a layer's step reaches, under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** active)


def count(cfg: dict, active: float, live_rows: float,
          selected_rows: float | None = None, ring_rows: float | None = None,
          experts_reached: float | None = None, wt_bytes: int = 2,
          kv_bytes: int = 2) -> dict:
    s = shapes(cfg)
    f, w = s["full"], s["sliding"]
    experts = s["L"] - s["dense"]
    each = live_rows / max(active, 1e-9)            # every slot alike
    if selected_rows is None:
        selected_rows = active * min(each, s["topk"])
    if ring_rows is None:
        ring_rows = active * min(each, s["behind"])
    if experts_reached is None:
        experts_reached = experts_read(s, active)
    dense_w = per_token_weights(s) - experts * s["router"]
    weights = (wt_bytes * (dense_w + s["H"] * s["V"])
               + 4 * experts * s["router"]
               + wt_bytes * experts * experts_reached * s["expert"])
    cache = kv_bytes * (
        s["fulls"] * (live_rows * s["Di"] + selected_rows * f["row"]
                      + active * (s["Di"] + f["row"]))
        + s["windows"] * (ring_rows + active) * w["row"])
    hit = active * s["K"] * s["held"] / s["E"]      # (token, choice) pairs here
    return {
        "bytes": weights + cache + active * s["H"] * wt_bytes,
        "flops": 2.0 * (per_token_weights(s) + s["H"] * s["V"]) * active
        + 2.0 * experts * s["expert"] * hit
        + s["fulls"] * (2.0 * s["Hi"] * s["Di"] * live_rows
                        + 2.0 * f["NH"] * selected_rows
                        * (f["R"] + f["Dr"] + f["R"]))
        + s["windows"] * 2.0 * w["NH"] * ring_rows
        * (w["R"] + w["Dr"] + w["R"])}
