"""Launcher of a window-and-full attention decoder with an expert layer (the
``afmoe`` block: Trinity): the program's ``models/window_moe.py`` behind
``serving_cell.MODELS``. Which family a registered model belongs to is the
type of its config (``models/families.py``), so there is nothing else to mark.

The configuration file's keys are the published ``config.json``'s, cut as its
``reduced`` says, plus two that state this chip's share of an expert-parallel
deployment: ``router_experts`` (the router's width: every published expert)
and ``experts_held`` ([first, count]; ``num_experts`` is that count).
"""

from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp

    from kukeon_tpu.models import window_moe

    first, count = config["experts_held"]
    if count != config["num_experts"] or config["num_shared_experts"] != 1 \
            or config["score_func"] != "sigmoid" or config["rope_scaling"] \
            or config["tie_word_embeddings"] \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise SystemExit(f"benchmark: {config['name']}: the window_moe "
                         "launcher cannot state this file's keys. No result.")
    return window_moe.WindowMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=(first, count),
        sliding_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        route_scale=float(config["route_scale"]),
        route_norm=bool(config["route_norm"]),
        max_seq_len=config["max_position_embeddings"],
        dtype=getattr(jnp, config["torch_dtype"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg


def abstract(config: dict) -> dict:
    import jax

    from kukeon_tpu.models import window_moe

    cfg = program_config(config)
    return {"cfg": cfg, "params": jax.eval_shape(
        lambda k: window_moe.init_params(k, cfg), jax.random.key(0))}
