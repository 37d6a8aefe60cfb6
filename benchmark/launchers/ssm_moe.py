"""Launcher of a decoder of Mamba-2 mixers with an attention layer among them
and an expert layer in every layer (the ``granitemoehybrid`` block: IBM's
Granite 4.0-H models): the program's ``models/ssm_moe.py`` behind
``serving_cell.MODELS``. Which family a registered model belongs to is the
type of its config (``models/families.py``), so there is nothing else to mark.

The configuration file's keys are the published ``config.json``'s, cut as its
``reduced`` says, plus two that state this chip's share of an expert-parallel
deployment: ``router_experts`` (the router's width: every published expert)
and ``experts_held`` ([first, count]; ``num_local_experts`` is that count).
``layer_types`` has to be whole periods with one attention layer each: the
program holds the pattern as a period and an offset. What the program's config
has no word for has to read as the program computes it, or the launcher ends
the run.
"""

from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp

    from kukeon_tpu.models import ssm_moe

    types = config["layer_types"]
    at = [i for i, t in enumerate(types) if t == "attention"]
    period = at[1] - at[0] if len(at) > 1 else len(types)
    first, count = config["experts_held"]
    heads, inner = config["mamba_n_heads"], config["mamba_d_head"]
    if count != config["num_local_experts"] or not at \
            or len(types) != config["num_hidden_layers"] \
            or types != [("attention" if i % period == at[0] else "mamba")
                         for i in range(len(types))] \
            or config["mamba_n_groups"] != 1 \
            or heads * inner != config["mamba_expand"] * config["hidden_size"] \
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["position_embedding_type"] != "nope" \
            or config["normalization_function"] != "rmsnorm" \
            or not config["tie_word_embeddings"]:
        raise SystemExit(f"benchmark: {config['name']}: the ssm_moe "
                         "launcher cannot state this file's keys. No result.")
    return ssm_moe.SsmMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        moe_intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_layers=config["num_hidden_layers"], attn_layer_period=period,
        attn_layer_offset=at[0],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], mamba_heads=heads, mamba_head_dim=inner,
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=(first, count),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        dtype=getattr(jnp, config["torch_dtype"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg


def abstract(config: dict) -> dict:
    import jax

    from kukeon_tpu.models import ssm_moe

    cfg = program_config(config)
    return {"cfg": cfg, "params": jax.eval_shape(
        lambda k: ssm_moe.init_params(k, cfg), jax.random.key(0))}
