"""Fixture: the launcher of a second family, added to a copy of the fixtures as
a new file. The program's ``models/moe.py`` (Mixtral block: the dense GQA trunk
with a softmax top-k expert layer in place of the SwiGLU) behind
``serving_cell.MODELS`` and its switch to that family, ``MOE_MODELS``. The
configuration file has two keys a dense file has not: ``num_local_experts``
and ``num_experts_per_tok``."""

from __future__ import annotations


def program_config(config: dict):
    import jax.numpy as jnp

    from kukeon_tpu.models import moe

    # capacity_factor stays the program's default: at 4 experts, top-2, the
    # serving path's buffer holds every token, so nothing is dropped.
    return moe.MoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=config["num_local_experts"],
        experts_per_token=config["num_experts_per_tok"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=getattr(jnp, config["torch_dtype"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg
    sc.MOE_MODELS.add(config["name"])


def abstract(config: dict) -> dict:
    import jax

    from kukeon_tpu.models import moe
    from kukeon_tpu.parallel import moe_specs_for_params

    cfg = program_config(config)
    params = jax.eval_shape(lambda k: moe.init_params(k, cfg),
                            jax.random.key(0))
    return {"cfg": cfg, "params": params, "forward_fn": moe.forward,
            "param_specs": moe_specs_for_params(params)}
