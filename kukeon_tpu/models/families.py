"""What a model family gives the serving path, as one record.

``runtime/serving_cell.py`` builds a cell from a configuration; which family
that configuration belongs to is the type of the config it registers, looked
up here. A record says how to boot the family without a checkpoint, how its
parameters are laid over a mesh, which forward the engine jits, and what a
slot's cache holds layer by layer:

- ``layered`` None: every layer holds ``S_max`` rows in one ``llama.KVCache``
  (contiguous or paged, bf16 or int8), written by ``forward`` itself; the
  prefix store, ``prefill_ext`` and the KV handoff work on that block.
- ``layered`` set: the layers are of several kinds (``models/kv_kinds.py``:
  a ring of a window's rows beside full stacks; a state-space layer's state,
  which has no rows at all, Mamba-1's or Mamba-2's; a latent row and an
  indexer's key with no head axis; a ring of a window layer's own latent row
  beside them). The family states its kinds and brings
  ``prefill`` and ``decode``; the engine owns insertion, the step's write and
  the valid rows.

A further family is a record here and a module beside this one, not another
set and another branch in the cell.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax


@dataclasses.dataclass(frozen=True)
class Layered:
    """The forwards of a family whose cache has several kinds of layers."""

    kinds: Callable[[Any, int], tuple]      # (cfg, max_seq_len) -> CacheKinds
    # Each forward hands back ONE tree shaped as the cache's kinds state it
    # (arrays by name: ``kv_kinds.names``), which the engine passes on whole.
    prefill: Callable       # (params, cfg, tokens [1, S], length) ->
    #                         (logits [V], block, counters); block: k, v
    #                         [L, 1, S, KV, D] (a named array's rows [L, 1, S,
    #                         width]) and the state at ``length``
    decode: Callable        # (params, cfg, tokens [B], view, kinds, active)
    #                         -> (logits [B, V], new, counters); new: k, v
    #                         [L, B, 1, KV, D] (named rows [L, B, 1, width])
    #                         and the state stacks, replaced
    # (cfg) -> what that config's forwards sum on the device, by metric name
    counters: Callable[[Any], tuple[str, ...]] = lambda cfg: ()


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    config_type: type
    # (cfg, seed, quantize, mesh) -> the served tree, checkpoint-less
    init_params: Callable
    forward: Callable | None = None         # llama.forward's signature
    param_specs: Callable | None = None     # params -> PartitionSpec tree
    # (path, cfg, quantize, max_seq_len) -> (params or stream, cfg)
    load_checkpoint: Callable | None = None
    layered: Layered | None = None
    # What the serving path may be asked for beyond the plain bf16 cell.
    supports: frozenset = frozenset()


# Named as the cell's flags name them; PREFIX is never refused: a family
# without it serves a prefixId as a counted miss and stores nothing.
INT8_WEIGHTS, INT8_KV, PAGED, PREFIX, MESH, CHECKPOINT = (
    "--dtype int8", "--kv-cache-int8", "--kv-page-tokens", "the prefix store",
    "--chips > 1", "--checkpoint")


def _dense_init(cfg, seed, quantize, mesh):
    from kukeon_tpu.models import llama
    from kukeon_tpu.parallel import sharding as shd

    key = jax.random.key(seed)
    if not quantize:
        return llama.init_params(key, cfg)
    # Directly in int8 on the device(s), every leaf born in its serving
    # sharding: an 8B bf16 tree (~16 GB) cannot be materialized on a 16 GB
    # chip just to be quantized (models/llama.py init_quantized_params).
    abstract = jax.eval_shape(
        lambda k: llama.init_quantized_params(k, cfg), key)
    return llama.init_quantized_params(
        key, cfg, shd.param_shardings(abstract, mesh))


def _moe_init(cfg, seed, quantize, mesh):
    from kukeon_tpu.models import moe

    if quantize:
        # On the host: a mixtral-8x7b bf16 tree (~93 GB) cannot be
        # materialized on-device just to be quantized.
        return moe.init_quantized_params_host(cfg, seed)
    return moe.init_params(jax.random.key(seed), cfg)


def _dense_load(path, cfg, quantize, max_seq_len):
    from kukeon_tpu.runtime.serving_cell import ServingCell

    return ServingCell._load_checkpoint(path, cfg, quantize)


def _moe_load(path, cfg, quantize, max_seq_len):
    from kukeon_tpu.models import hf_convert, moe

    params, cfg = hf_convert.load_moe_params(path, dtype=cfg.dtype)
    if max_seq_len:
        cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
    # Weights-only int8 (router/norms stay high precision); dequant fuses
    # into attention mm and expert einsums.
    return (moe.quantize_params(params) if quantize else params), cfg


def _moe_specs(params):
    from kukeon_tpu.parallel import moe_specs_for_params

    return moe_specs_for_params(params)


def _drawn_init(model):
    """The checkpoint-less boot of a family whose module draws its tree in
    one jitted program (``init_params``) and lays it out by ``param_specs``."""
    def init(cfg, seed, quantize, mesh):
        from kukeon_tpu.parallel import sharding as shd

        key = jax.random.key(seed)
        abstract = jax.eval_shape(lambda k: model.init_params(k, cfg), key)
        return model.init_params(key, cfg, shd.param_shardings(
            abstract, mesh, specs=model.param_specs(abstract)))

    return init


@functools.cache
def _families() -> tuple[Family, ...]:
    from kukeon_tpu.models import (llama, moe, sparse_latent_moe, ssm_hybrid,
                                   ssm_moe, window_moe)

    return (
        Family("dense_gqa", llama.LlamaConfig, _dense_init,
               forward=llama.forward, load_checkpoint=_dense_load,
               supports=frozenset({INT8_WEIGHTS, INT8_KV, PAGED, PREFIX,
                                   MESH, CHECKPOINT})),
        # int8 KV is a llama-decode-path feature the MoE forward lacks.
        Family("moe_softmax_topk", moe.MoEConfig, _moe_init,
               forward=moe.forward, param_specs=_moe_specs,
               load_checkpoint=_moe_load,
               supports=frozenset({INT8_WEIGHTS, PAGED, PREFIX, MESH,
                                   CHECKPOINT})),
        Family("window_moe", window_moe.WindowMoEConfig,
               _drawn_init(window_moe),
               param_specs=window_moe.param_specs,
               layered=Layered(
                   kinds=window_moe.WindowMoEConfig.cache_kinds,
                   prefill=window_moe.prefill, decode=window_moe.decode,
                   counters=lambda cfg: window_moe.COUNTERS)),
        Family("ssm_hybrid", ssm_hybrid.SsmHybridConfig,
               _drawn_init(ssm_hybrid),
               param_specs=ssm_hybrid.param_specs,
               layered=Layered(
                   kinds=ssm_hybrid.SsmHybridConfig.cache_kinds,
                   prefill=ssm_hybrid.prefill, decode=ssm_hybrid.decode)),
        Family("sparse_latent_moe", sparse_latent_moe.SparseLatentMoEConfig,
               _drawn_init(sparse_latent_moe),
               param_specs=sparse_latent_moe.param_specs,
               layered=Layered(
                   kinds=sparse_latent_moe.SparseLatentMoEConfig.cache_kinds,
                   prefill=sparse_latent_moe.prefill,
                   decode=sparse_latent_moe.decode,
                   counters=sparse_latent_moe.counters)),
        Family("ssm_moe", ssm_moe.SsmMoEConfig, _drawn_init(ssm_moe),
               param_specs=ssm_moe.param_specs,
               layered=Layered(
                   kinds=ssm_moe.SsmMoEConfig.cache_kinds,
                   prefill=ssm_moe.prefill, decode=ssm_moe.decode,
                   counters=lambda cfg: ssm_moe.COUNTERS)),
    )


def find(cfg) -> Family | None:
    """The family of a program config, by its type; None for a config no
    record names (an engine built around a forward of the caller's own)."""
    for family in _families():
        if type(cfg) is family.config_type:
            return family
    return None


def of(cfg) -> Family:
    family = find(cfg)
    if family is None:
        raise SystemExit(f"no model family serves a {type(cfg).__name__}")
    return family


def refuse(family: Family, model: str, asked: dict[str, bool]) -> None:
    """End the boot, loudly, where a cell asks this family for what it does
    not have: serving it wrongly is not an option. ``asked``: feature ->
    whether the cell wants it."""
    missing = sorted(f for f, wanted in asked.items()
                     if wanted and f not in family.supports)
    if missing:
        raise SystemExit(
            f"model {model!r} (family {family.name}) does not support "
            f"{', '.join(missing)} yet")
