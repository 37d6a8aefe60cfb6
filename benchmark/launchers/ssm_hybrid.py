"""Launcher of a decoder of state-space layers with an attention layer among
them (the ``jamba`` block: AI21's Jamba models with a dense feed-forward): the
program's ``models/ssm_hybrid.py`` behind ``serving_cell.MODELS``. Which family
a registered model belongs to is the type of its config
(``models/families.py``), so there is nothing else to mark.

The configuration file's keys are the published ``config.json``'s. What the
program's config has no word for has to read as the program computes it, or
the launcher ends the run: one expert (a dense SwiGLU in every layer), tied
embeddings, a convolution with a bias, projections without, no window.
"""

from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp

    from kukeon_tpu.models import ssm_hybrid

    if config["num_experts"] != 1 or not config["tie_word_embeddings"] \
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"] \
            or config["sliding_window"] or config["hidden_act"] != "silu" \
            or config["hidden_size"] % config["num_attention_heads"]:
        raise SystemExit(f"benchmark: {config['name']}: the ssm_hybrid "
                         "launcher cannot state this file's keys. No result.")
    return ssm_hybrid.SsmHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"], expand=config["mamba_expand"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        dtype=getattr(jnp, config["torch_dtype"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg


def abstract(config: dict) -> dict:
    import jax

    from kukeon_tpu.models import ssm_hybrid

    cfg = program_config(config)
    return {"cfg": cfg, "params": jax.eval_shape(
        lambda k: ssm_hybrid.init_params(k, cfg), jax.random.key(0))}
