"""Launcher of a decoder whose window layers have a latent attention of their
own among latent layers that select their rows (the ``dots3_note`` block:
dots3-note-prev): the program's ``models/sparse_latent_moe.py`` behind
``serving_cell.MODELS``, the family that has the selecting latent block, with
a layer's attention stated as data. Which family a registered model belongs
to is the type of its config (``models/families.py``), so there is nothing
else to mark. A program whose family has no such layer (an older commit) ends
here with "No result".

The configuration file's keys are the published ``config.json``'s, cut as its
``reduced`` says, plus two that state this chip's share of an expert-parallel
deployment: ``router_experts`` (the router's width: every published expert)
and ``experts_held`` ([first, count]; ``n_routed_experts`` is that count).
"""

from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp

    try:
        from kukeon_tpu.models import sparse_latent_moe as model
        from kukeon_tpu.models.sparse_latent_moe import LatentAttention
    except ImportError as e:
        raise SystemExit(f"benchmark: {config['name']}: this program's "
                         f"latent family states no attention a layer ({e}). "
                         "No result.")

    first, count = config["experts_held"]
    if count != config["n_routed_experts"] or config["n_shared_experts"] != 1 \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["moe_layer_freq"] != 1 \
            or config["attention_gate_type"] != "headwise" \
            or config["swa_attention_gate_type"] != "headwise" \
            or len(config["layer_types"]) != config["num_hidden_layers"] \
            or config["num_key_value_heads"] != config["num_attention_heads"] \
            or (config["swa_num_key_value_heads"]
                != config["swa_num_attention_heads"]):
        raise SystemExit(f"benchmark: {config['name']}: the mixed_latent_moe "
                         "launcher cannot state this file's keys. No result.")
    return model.SparseLatentMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=(first, count), n_group=1, topk_group=1,
        route_scale=float(config["routed_scaling_factor"]),
        route_norm=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]), rope_factor=1.0,
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        dtype=getattr(jnp, config["torch_dtype"]),
        layer_types=tuple(config["layer_types"]),
        sliding=LatentAttention(
            num_heads=config["swa_num_attention_heads"],
            q_lora_rank=config["swa_q_lora_rank"],
            kv_lora_rank=config["swa_kv_lora_rank"],
            qk_nope_head_dim=config["swa_qk_nope_head_dim"],
            qk_rope_head_dim=config["swa_qk_rope_head_dim"],
            v_head_dim=config["swa_v_head_dim"],
            rope_theta=float(config["swa_rope_theta"]),
            window=config["sliding_window_size"]),
        head_gate=True,
        lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg


def abstract(config: dict) -> dict:
    import jax

    from kukeon_tpu.models import sparse_latent_moe as model

    cfg = program_config(config)
    return {"cfg": cfg, "params": jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.key(0))}
