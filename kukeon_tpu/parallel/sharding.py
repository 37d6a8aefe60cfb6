"""Sharding rules: param/activation PartitionSpecs for the Llama family.

GSPMD-style: we annotate shardings on the pytrees and jit boundaries and let
XLA insert the collectives (all-gather / reduce-scatter / all-reduce over
ICI). The megatron pattern for one transformer block needs exactly one
all-reduce per attention block and one per MLP block in forward:

  - wq/wk/wv and w_gate/w_up are sharded on their *output* dim ('tensor'),
  - wo and w_down are sharded on their *input* dim ('tensor'),

so the pair (column-parallel -> row-parallel) keeps activations sharded by
head/intermediate between them, with a single psum at the end of each block.
The embedding is vocab-sharded; the final projection gathers logits.

FSDP shards every weight's largest remaining dim over 'fsdp'; XLA turns that
into per-layer all-gathers (forward) and reduce-scatters (backward), which
overlap with compute on TPU.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kukeon_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
)


def llama_param_specs(fsdp: bool = False) -> dict:
    """PartitionSpec pytree matching the layout of models.llama.init_params.

    Stacked-layer weights have a leading [L] axis that is always replicated
    (the scan iterates over it).
    """
    f = AXIS_FSDP if fsdp else None
    t = AXIS_TENSOR
    specs = {
        "embed": P(t, f),                       # vocab-sharded
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, f, t),                # column-parallel (heads)
            "wk": P(None, f, t),
            "wv": P(None, f, t),
            "wo": P(None, t, f),                # row-parallel
            "mlp_norm": P(None, None),
            "w_gate": P(None, f, t),            # column-parallel (intermediate)
            "w_up": P(None, f, t),
            "w_down": P(None, t, f),            # row-parallel
        },
        "final_norm": P(None),
    }
    # lm_head present only for untied configs; caller prunes to the actual tree.
    specs["lm_head"] = P(f, t)
    return specs


def specs_for_params(params, fsdp: bool = False) -> dict:
    """Prune the full spec tree to the keys present in ``params``."""
    full = llama_param_specs(fsdp)
    return {k: full[k] for k in params}


def _quant_scale_spec(spec: P, q, s) -> P:
    """Spec for an int8 scale vector: the matrix spec minus the contracted
    axis (scale spans the non-contracted axis/axes)."""
    if q.ndim == 4:                      # experts [L, E, in, out] -> s [L, E, out]
        return P(spec[0], spec[1], spec[3])
    if q.ndim == 3:                      # stacked [L, in, out] -> s [L, out]
        return P(spec[0], spec[2])
    # 2-D: s aligns with whichever matrix axis it matches in size.
    return P(spec[0] if s.shape[0] == q.shape[0] else spec[1])


def param_shardings(params, mesh: Mesh, fsdp: bool = False, specs=None):
    """NamedSharding pytree matching ``params``' structure (quantized
    {"q","s"} leaves expanded), without touching any device. ``specs``
    overrides the Llama defaults (e.g. moe_specs_for_params)."""
    if specs is None:
        specs = specs_for_params(params, fsdp)

    def expand(spec, leaf):
        if isinstance(leaf, dict) and "q" in leaf:
            return {
                "q": NamedSharding(mesh, spec),
                "s": NamedSharding(
                    mesh, _quant_scale_spec(spec, leaf["q"], leaf["s"])
                ),
            }
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        expand, specs, params,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params, mesh: Mesh, fsdp: bool = False, threads: int = 4,
                 specs=None):
    """Device-put a param pytree with the canonical shardings.

    Quantized leaves ({"q": int8 matrix, "s": scale}) inherit the matrix
    spec for q; the scale shards with the matrix's surviving axes.

    Transfers are issued from a small thread pool, so the per-transfer
    dispatch latency of the tree's leaves overlaps."""
    shardings = param_shardings(params, mesh, fsdp, specs=specs)
    flat_s, treedef = jax.tree.flatten(shardings)
    flat_p, _ = jax.tree.flatten(params)

    if threads <= 1 or len(flat_p) < 8:
        out = [jax.device_put(x, s) for x, s in zip(flat_p, flat_s)]
    else:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=threads) as pool:
            out = list(pool.map(
                lambda xs: jax.device_put(xs[0], xs[1]),
                zip(flat_p, flat_s),
            ))
    return jax.tree.unflatten(treedef, out)


def moe_param_specs(fsdp: bool = False) -> dict:
    """PartitionSpec pytree matching models.moe.init_params.

    The attention trunk shards exactly like Llama; the expert weights put
    their E axis on ``expert`` (each chip owns E/ep experts — GSPMD turns
    the dispatch/combine einsums into all-to-alls) and keep the megatron
    column->row pairing on ``tensor`` within each expert. The router is
    tiny and replicated."""
    f = AXIS_FSDP if fsdp else None
    t = AXIS_TENSOR
    e = AXIS_EXPERT
    specs = {
        "embed": P(t, f),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, f, t),
            "wk": P(None, f, t),
            "wv": P(None, f, t),
            "wo": P(None, t, f),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, e, f, t),          # [L, E, H, I]
            "w_up": P(None, e, f, t),
            "w_down": P(None, e, t, f),          # [L, E, I, H]
        },
        "final_norm": P(None),
    }
    specs["lm_head"] = P(f, t)
    return specs


def moe_specs_for_params(params, fsdp: bool = False) -> dict:
    full = moe_param_specs(fsdp)
    return {k: full[k] for k in params}


def bert_param_specs(fsdp: bool = False) -> dict:
    """PartitionSpec pytree matching models.bert.init_params — the same
    megatron column->row pairing as the decoder: qkv/in projections shard
    their output dim on 'tensor', wo/out their input dim, one psum per
    block. Biases follow their matmul's output sharding."""
    f = AXIS_FSDP if fsdp else None
    t = AXIS_TENSOR
    return {
        "embed": {
            "word": P(t, f),                    # vocab-sharded
            "position": P(None, f),
            "type": P(None, f),
            "norm_scale": P(None),
            "norm_bias": P(None),
        },
        "layers": {
            "wq": P(None, f, t), "bq": P(None, t),
            "wk": P(None, f, t), "bk": P(None, t),
            "wv": P(None, f, t), "bv": P(None, t),
            "wo": P(None, t, f), "bo": P(None, None),
            "attn_norm_scale": P(None, None), "attn_norm_bias": P(None, None),
            "w_in": P(None, f, t), "b_in": P(None, t),
            "w_out": P(None, t, f), "b_out": P(None, None),
            "mlp_norm_scale": P(None, None), "mlp_norm_bias": P(None, None),
        },
    }


def shard_bert_params(params, mesh: Mesh, fsdp: bool = False):
    specs = bert_param_specs(fsdp)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(params, shardings)


def batch_spec() -> P:
    """Tokens/positions: batch over (data, fsdp), sequence over seq axis."""
    return P((AXIS_DATA, AXIS_FSDP), AXIS_SEQ)


def kv_cache_spec(shard_batch: bool = False) -> "P":
    """KVCache k/v [L, B, S, KV, D]: kv-heads on tensor; optionally batch on
    data/fsdp (training-style). A serving engine is one model replica, so its
    decode slots stay replicated — data parallelism means multiple engines."""
    batch = (AXIS_DATA, AXIS_FSDP) if shard_batch else None
    return P(None, batch, None, AXIS_TENSOR, None)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
