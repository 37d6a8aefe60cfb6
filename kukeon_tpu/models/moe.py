"""Mixtral-style sparse Mixture-of-Experts decoder, functional JAX.

Second model family of the in-tree serving/training path (the reference
runtime has no model math — SURVEY.md §2.9; this widens the TPU build's
model zoo alongside :mod:`kukeon_tpu.models.llama` and gives the ``expert``
mesh axis a real workload).

TPU-first design:

- **Same attention trunk as Llama** (GQA + RoPE + RMSNorm, stacked layers
  under ``lax.scan``, the shared KVCache layout) — the MoE block replaces
  only the dense SwiGLU MLP, exactly like Mixtral-vs-Mistral.
- **Dense-dispatch MoE (GShard/Switch formulation)**: routing is expressed
  as two einsums against a static-capacity one-hot dispatch tensor instead
  of gather/scatter with dynamic shapes. Everything is a fixed-shape batched
  matmul over a leading ``E`` axis — MXU-friendly, one compiled program —
  and sharding ``E`` over the mesh's ``expert`` axis makes GSPMD insert the
  dispatch/combine all-to-alls over ICI.
- **Static capacity**: each expert processes at most
  ``capacity_factor * tokens * top_k / num_experts`` tokens; overflow tokens
  fall through the residual (standard GShard semantics). Tests use a
  capacity factor that guarantees no drops when checking numerics.
- **Aux losses for training**: Switch load-balance loss + router z-loss,
  returned by :func:`forward_with_aux`; :func:`forward` keeps the exact
  serving signature of ``llama.forward`` (logits, cache).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kukeon_tpu.models import llama
from kukeon_tpu.models.llama import KVCache, cache_insert, embed, mm
from kukeon_tpu.ops.attention import gqa_attention
from kukeon_tpu.ops.norms import rms_norm
from kukeon_tpu.ops.rope import apply_rope

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 2.0
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    router_z_coef: float = 1e-3
    load_balance_coef: float = 1e-2

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def mixtral_8x7b() -> MoEConfig:
    """Mixtral-8x7B shapes (public architecture)."""
    return MoEConfig()


def moe_tiny() -> MoEConfig:
    """Test-size config: fast on a CPU mesh; 4 experts so expert=2 shards."""
    return MoEConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=4, experts_per_token=2, capacity_factor=8.0,
        rope_theta=10_000.0, max_seq_len=256, dtype=jnp.float32,
        tie_embeddings=True,
    )


def init_params(key: jax.Array, cfg: MoEConfig) -> Params:
    """Random-init. Layout (stacked layers axis 0, experts axis 1):

      embed:   [V, H]
      layers:  attn_norm/mlp_norm [L, H], wq [L, H, NH*D], wk/wv [L, H, KV*D],
               wo [L, NH*D, H], router [L, H, E],
               w_gate/w_up [L, E, H, I], w_down [L, E, I, H]
      final_norm: [H];  lm_head: [H, V] (absent when tie_embeddings)
    """
    c = cfg
    keys = iter(jax.random.split(key, 16))

    def dense(k, shape, fan_in):
        scale = fan_in ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(c.dtype)

    L, H, I, V, E = (c.num_layers, c.hidden_size, c.intermediate_size,
                     c.vocab_size, c.num_experts)
    params: Params = {
        "embed": dense(next(keys), (V, H), H),
        "layers": {
            "attn_norm": jnp.ones((L, H), c.dtype),
            "wq": dense(next(keys), (L, H, c.q_dim), H),
            "wk": dense(next(keys), (L, H, c.kv_dim), H),
            "wv": dense(next(keys), (L, H, c.kv_dim), H),
            "wo": dense(next(keys), (L, c.q_dim, H), c.q_dim),
            "mlp_norm": jnp.ones((L, H), c.dtype),
            # Router in f32: tiny, and routing decisions should not wobble
            # with the activation dtype.
            "router": jax.random.normal(next(keys), (L, H, E), jnp.float32) * (H ** -0.5),
            "w_gate": dense(next(keys), (L, E, H, I), H),
            "w_up": dense(next(keys), (L, E, H, I), H),
            "w_down": dense(next(keys), (L, E, I, H), I),
        },
        "final_norm": jnp.ones((H,), c.dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (H, V), H)
    return params


def quantize_params(params: Params) -> Params:
    """bf16 MoE pytree -> int8 ({"q", "s"} leaves for every dense matrix).

    Attention/embed quantize exactly like the Llama tree (llama.mm
    consumes them); expert stacks [L, E, in, out] quantize per output
    channel along the contraction axis (s: [L, E, out], applied fused in
    the expert einsums). The router stays f32 — it is tiny and routing
    decisions must not wobble with quantization noise. Weights-only int8
    halves HBM bytes/token, the decode bottleneck (mixtral-8x7b: ~93 GB
    bf16 -> ~47 GB int8 across a v5e-8)."""

    def q(w, axis):
        qw, s = llama._int8_sym(w, axis)
        return {"q": qw, "s": jnp.squeeze(s, axis=axis)}

    L = params["layers"]
    out: Params = {
        "embed": q(params["embed"], 1),
        "layers": {
            "attn_norm": L["attn_norm"],
            "wq": q(L["wq"], 1), "wk": q(L["wk"], 1), "wv": q(L["wv"], 1),
            "wo": q(L["wo"], 1),
            "mlp_norm": L["mlp_norm"],
            "router": L["router"],
            "w_gate": q(L["w_gate"], 2),       # [L, E, H, I] -> s [L, E, I]
            "w_up": q(L["w_up"], 2),
            "w_down": q(L["w_down"], 2),       # [L, E, I, H] -> s [L, E, H]
        },
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"], 0)
    return out


def init_quantized_params_host(cfg: MoEConfig, seed: int = 0) -> Params:
    """Random-init DIRECTLY in int8 on the host, leaf by leaf: a
    mixtral-8x7b bf16 tree is ~93 GB — it cannot be materialized on a
    16 GB chip just to be quantized."""
    import numpy as np

    from kukeon_tpu.models.llama import quantize_np

    c = cfg
    rng = np.random.default_rng(seed)
    L, H, I, V, E = (c.num_layers, c.hidden_size, c.intermediate_size,
                     c.vocab_size, c.num_experts)
    ndtype = np.dtype(c.dtype)

    def q(shape, fan_in, axis):
        w = rng.standard_normal(shape, np.float32) * (fan_in ** -0.5)
        return quantize_np(w, axis)

    params: Params = {
        "embed": q((V, H), H, 1),
        "layers": {
            "attn_norm": np.ones((L, H), ndtype),
            "wq": q((L, H, c.q_dim), H, 1),
            "wk": q((L, H, c.kv_dim), H, 1),
            "wv": q((L, H, c.kv_dim), H, 1),
            "wo": q((L, c.q_dim, H), c.q_dim, 1),
            "mlp_norm": np.ones((L, H), ndtype),
            "router": (rng.standard_normal((L, H, E), np.float32)
                       * (H ** -0.5)),
            "w_gate": q((L, E, H, I), H, 2),
            "w_up": q((L, E, H, I), H, 2),
            "w_down": q((L, E, I, H), I, 2),
        },
        "final_norm": np.ones((H,), ndtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = q((H, V), H, 0)
    return params


def _expert_mm(x: jnp.ndarray, w, eq: str) -> jnp.ndarray:
    """Per-expert batched matmul ('ech,ehi->eci' or 'eci,eih->ech') for
    plain or int8 ({"q","s"}) expert stacks; dequant fuses into the dot."""
    if llama._is_q(w):
        raw = jnp.einsum(eq, x, w["q"].astype(x.dtype))
        return raw * w["s"][:, None, :].astype(x.dtype)
    return jnp.einsum(eq, x, w)


def _capacity(cfg: MoEConfig, n_tokens: int, inference: bool = False) -> int:
    """Per-expert token capacity.

    Training uses the GShard drop policy (capacity_factor × fair share;
    overflow tokens fall through the residual — standard, and the
    load-balance loss keeps drops rare). Inference must not silently drop
    expert compute (reference Mixtral always runs both top-k experts):
    decode-sized batches get FULL capacity (C = N, exact for any routing —
    the dispatch tensor is a few KB), and prefill gets a 2× wider buffer
    than training, making drops possible only under extreme routing
    concentration (>8× the fair share for the 8x7B config)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    if inference:
        if n_tokens <= 64:
            return n_tokens
        factor = max(cfg.capacity_factor, 2.0) * 2.0
        return min(n_tokens, max(int(factor * n_tokens * K / E), K))
    cap = int(cfg.capacity_factor * n_tokens * K / E)
    return max(cap, K)


def moe_block(h: jnp.ndarray, w: dict, cfg: MoEConfig,
              inference: bool = False) -> tuple[jnp.ndarray, dict]:
    """Sparse-MoE SwiGLU over [B, S, H] -> ([B, S, H], aux losses).

    GShard dense-dispatch: top-k routing -> static-capacity one-hot dispatch
    tensor -> two einsums around batched per-expert matmuls. All shapes are
    static; with ``w_gate``'s E axis sharded on the mesh's ``expert`` axis,
    XLA partitions the expert matmuls per chip and inserts all-to-alls for
    the dispatch/combine einsums.
    """
    c = cfg
    B, S, H = h.shape
    N = B * S
    E, K = c.num_experts, c.experts_per_token
    C = _capacity(c, N, inference)
    x = h.reshape(N, H)

    router_logits = x.astype(jnp.float32) @ w["router"]          # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)              # [N, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Priority dispatch: choice slot 0 of every token beats slot 1 (GShard).
    # mask: [K, N, E]; position_in_expert via a cumulative count over the
    # flattened (K, N) order.
    mask = jax.nn.one_hot(expert_idx.T, E, dtype=jnp.float32)    # [K, N, E]
    flat = mask.reshape(K * N, E)
    pos = jnp.cumsum(flat, axis=0) - flat                        # tokens ahead
    keep = (pos < C).astype(jnp.float32) * flat                  # drop overflow
    # dispatch [N, E, C]: one-hot of each kept (token, choice) -> its slot.
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    dispatch = (keep[..., None] * slot).reshape(K, N, E, C).sum(axis=0)
    combine = dispatch * (
        (mask * gate_vals.T[..., None]).sum(axis=0)[..., None]   # [N, E, 1]
    )

    # Dispatch -> per-expert batches -> SwiGLU -> combine.
    xe = jnp.einsum("nec,nh->ech", dispatch, x).astype(c.dtype)  # [E, C, H]
    gate = jax.nn.silu(
        _expert_mm(xe, w["w_gate"], "ech,ehi->eci").astype(jnp.float32)
    ).astype(c.dtype)
    up = _expert_mm(xe, w["w_up"], "ech,ehi->eci")
    ye = _expert_mm(gate * up, w["w_down"], "eci,eih->ech")  # [E, C, H]
    y = jnp.einsum("nec,ech->nh", combine.astype(c.dtype), ye)

    # Aux losses (f32): Switch load-balance (E * sum_e f_e * P_e; 1.0 at
    # perfect balance) over FIRST-choice assignments, + router z-loss.
    f = jnp.mean(mask[0], axis=0)                                # [E]
    p = jnp.mean(probs, axis=0)                                  # [E]
    lb = E * jnp.sum(f * p)
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return y.reshape(B, S, H), {"load_balance": lb, "router_z": z}


def _decode_forward(
    params: Params,
    c: MoEConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache,
    B: int,
    active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Single-token decode, HBM-optimal (mirrors llama._decode_forward: the
    layer scan reads the cache as a read-only input and emits only the tiny
    per-layer new K/V; the cache is updated once per step with per-slot
    in-place slice writes — cache bytes stream through HBM exactly once).
    The MoE block runs at N = B tokens, where dense dispatch is a few KB
    and capacity is exact (no drops)."""
    from kukeon_tpu.ops.attention import decode_gqa_attention

    offsets = cache.lengths
    reads = offsets if active is None else jnp.where(active, offsets, 0)

    def layer_step(x, layer):
        w, i = layer
        h = rms_norm(x, w["attn_norm"], c.rms_norm_eps)
        q = mm(h, w["wq"]).reshape(B, 1, c.num_heads, c.head_dim)
        k = mm(h, w["wk"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
        v = mm(h, w["wv"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)

        attn = decode_gqa_attention(q, k, v, cache.k, cache.v, i, reads)
        x = x + mm(attn.reshape(B, 1, c.q_dim), w["wo"])

        h = rms_norm(x, w["mlp_norm"], c.rms_norm_eps)
        y, _ = moe_block(h, w, c, inference=True)
        return x + y, (k, v)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], jnp.arange(cache.k.shape[0])))
    k_upd, v_upd = cache.k, cache.v
    for b in range(B):
        start = (0, b, offsets[b], 0, 0)
        k_upd = jax.lax.dynamic_update_slice(k_upd, new_k[:, b : b + 1], start)
        v_upd = jax.lax.dynamic_update_slice(v_upd, new_v[:, b : b + 1], start)
    new_cache = KVCache(k=k_upd, v=v_upd, lengths=cache.lengths + 1)

    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return llama._logits(params, c, x), new_cache


def forward_with_aux(
    params: Params,
    cfg: MoEConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache | None = None,
    attn_impl: str = "auto",
    logit_positions: jnp.ndarray | None = None,
    active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache | None, dict]:
    """Run the MoE decoder; returns (logits, cache', aux-loss dict).

    Cache semantics identical to ``llama.forward`` (same KVCache layout, so
    the serving engine's insert/decode programs carry over unchanged);
    ``logit_positions`` [B] restricts the LM head to one position per
    sequence exactly as in ``llama.forward`` (logits come back [B, 1, V]),
    and ``active`` [B] keeps the other slots of a decode step off the cache.

    A cache marks the inference path: expert capacity switches to the
    no-drop/wide policy (see :func:`_capacity`) — serving must not silently
    zero overflow tokens' expert compute the way the training drop policy
    legitimately does."""
    c = cfg
    B, S = tokens.shape
    inference = cache is not None
    x = embed(params, tokens, c.dtype)

    if cache is not None and S == 1 and attn_impl in ("auto", "reference"):
        logits, new_cache = _decode_forward(params, c, x, positions, cache, B,
                                            active)
        return logits, new_cache, {"load_balance": jnp.float32(0.0),
                                   "router_z": jnp.float32(0.0)}

    offsets = cache.lengths if cache is not None else None

    def layer_step(carry, layer):
        x, lb_sum, z_sum = carry
        w, layer_cache = layer
        h = rms_norm(x, w["attn_norm"], c.rms_norm_eps)
        q = mm(h, w["wq"]).reshape(B, S, c.num_heads, c.head_dim)
        k = mm(h, w["wk"]).reshape(B, S, c.num_kv_heads, c.head_dim)
        v = mm(h, w["wv"]).reshape(B, S, c.num_kv_heads, c.head_dim)
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)

        if layer_cache is not None:
            ck, cv = layer_cache
            ck = cache_insert(ck, k, offsets)
            cv = cache_insert(cv, v, offsets)
            kv_positions = jnp.broadcast_to(
                jnp.arange(ck.shape[1], dtype=jnp.int32)[None, :], (B, ck.shape[1])
            )
            attn = gqa_attention(
                q, ck, cv,
                q_positions=positions, kv_positions=kv_positions,
                kv_length=offsets + S, impl=attn_impl,
            )
            new_layer_cache = (ck, cv)
        else:
            attn = gqa_attention(
                q, k, v,
                q_positions=positions, kv_positions=positions, impl=attn_impl,
            )
            new_layer_cache = None

        x = x + mm(attn.reshape(B, S, c.q_dim), w["wo"])

        h = rms_norm(x, w["mlp_norm"], c.rms_norm_eps)
        y, aux = moe_block(h, w, c, inference=inference)
        x = x + y
        return (x, lb_sum + aux["load_balance"], z_sum + aux["router_z"]), new_layer_cache

    layer_ws = params["layers"]
    init = (x, jnp.float32(0.0), jnp.float32(0.0))
    if cache is not None:
        (x, lb, z), (new_k, new_v) = jax.lax.scan(
            lambda carry, layer: layer_step(carry, (layer[0], (layer[1], layer[2]))),
            init, (layer_ws, cache.k, cache.v),
        )
        new_cache = KVCache(k=new_k, v=new_v, lengths=cache.lengths + S)
    else:
        (x, lb, z), _ = jax.lax.scan(
            lambda carry, w: layer_step(carry, (w, None)), init, layer_ws
        )
        new_cache = None

    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    if logit_positions is not None:
        x = jnp.take_along_axis(x, logit_positions[:, None, None], axis=1)
    logits = llama._logits(params, c, x)
    aux = {"load_balance": lb / c.num_layers, "router_z": z / c.num_layers}
    return logits, new_cache, aux


def forward(
    params: Params,
    cfg: MoEConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache | None = None,
    attn_impl: str = "auto",
    logit_positions: jnp.ndarray | None = None,
    active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache | None]:
    """Serving-signature forward (drop-in for ``llama.forward``)."""
    logits, new_cache, _ = forward_with_aux(
        params, cfg, tokens, positions, cache, attn_impl, logit_positions,
        active,
    )
    return logits, new_cache
