"""Launcher of a dense GQA decoder (Mistral / Codestral block): the program's
``models/llama.py`` behind ``serving_cell.MODELS``.

A configuration file names its family once (``"reference": "<family>"``); that
name finds ``reference/<family>.py`` and this file's sibling
``launchers/<family>.py``, beside the configuration or under ``benchmark/``.
A launcher is two functions of the configuration file's dict:

  register(config)  enter ``config["name"]`` in the program's own registry, so
                    that ``ServingCell(config["name"], ...)`` builds this
                    configuration (a second family also sets whatever marks it
                    as one). ``cell_main.CellHost.boot`` builds the cell itself
                    and pins every engine lever from ``serving``: a launcher
                    has no way to pass one.
  abstract(config)  for ``rehearse_compile.py``: ``{"cfg": the program's
                    config, "params": the abstract tree of the served
                    parameters}`` plus whatever else ``ServingEngine`` needs to
                    run this family (``forward_fn``, ``param_specs``); a lever
                    among them is a TypeError there.
"""

from __future__ import annotations


def program_config(config: dict):
    """The configuration file's published sizes as the program's config."""
    import jax.numpy as jnp

    from kukeon_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=getattr(jnp, config["torch_dtype"]))


def register(config: dict) -> None:
    from kukeon_tpu.runtime import serving_cell as sc

    cfg = program_config(config)
    sc.MODELS[config["name"]] = lambda: cfg


def abstract(config: dict) -> dict:
    """The tree of a checkpoint-less weights-only int8 boot, which is what
    every dense configuration's ``serving`` states."""
    import jax

    from kukeon_tpu.models import llama

    cfg = program_config(config)
    return {"cfg": cfg, "params": jax.eval_shape(
        lambda k: llama.init_quantized_params(k, cfg), jax.random.key(0))}
