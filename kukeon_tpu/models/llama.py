"""Llama-family decoder model, functional JAX.

This is the flagship model of the in-tree serving path (BASELINE.json north
star: Llama-3-8B agent serving on a v5e slice). Design points, TPU-first:

- **Pure functional**: params are a plain pytree dict; the forward is a pure
  function — trivially jittable, shardable, and checkpointable.
- **Stacked layers + ``lax.scan``**: all transformer blocks share one set of
  stacked weights ([L, ...] leading axis) and run under ``lax.scan``, so
  compile time and HLO size are O(1) in depth instead of O(L).
- **bf16 weights/activations, f32 softmax & norms**: keeps matmuls on the MXU
  while reductions stay numerically stable.
- **GQA + RoPE + SwiGLU**: Llama-3 architecture (also covers Llama-2 shapes).
- **Cache-aware**: the same ``forward`` covers prefill (no cache), cached
  prefill, and single-token decode; cache layout is [L, B, S, KV, D] so the
  scan carries per-layer cache slices.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kukeon_tpu.ops import dispatch
from kukeon_tpu.ops.attention import gqa_attention
from kukeon_tpu.ops.norms import rms_norm
from kukeon_tpu.ops.rope import apply_rope

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def param_count(self) -> int:
        embed = self.vocab_size * self.hidden_size
        attn = self.hidden_size * (self.q_dim + 2 * self.kv_dim) + self.q_dim * self.hidden_size
        mlp = 3 * self.hidden_size * self.intermediate_size
        norms = 2 * self.hidden_size
        head = 0 if self.tie_embeddings else embed
        return embed + self.num_layers * (attn + mlp + norms) + self.hidden_size + head


# --- Presets -----------------------------------------------------------------

def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama3_1b() -> LlamaConfig:
    """Llama-3.2-1B shapes — fits one v5e chip in bf16 with headroom."""
    return LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        tie_embeddings=True,
    )


def llama_tiny() -> LlamaConfig:
    """Test-size config: runs fast on a CPU mesh."""
    return LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        rope_theta=10_000.0, max_seq_len=256, dtype=jnp.float32,
        tie_embeddings=True,
    )


# --- Init --------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Random-init a parameter pytree.

    Layout (stacked layers on axis 0):
      embed:   [V, H]
      layers:  attn_norm [L, H], wq [L, H, NH*D], wk/wv [L, H, KV*D],
               wo [L, NH*D, H], mlp_norm [L, H],
               w_gate/w_up [L, H, I], w_down [L, I, H]
      final_norm: [H]
      lm_head: [H, V] (absent when tie_embeddings)
    """
    c = cfg
    keys = iter(jax.random.split(key, 16))

    def dense(k, shape, fan_in):
        scale = fan_in ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(c.dtype)

    L, H, I, V = c.num_layers, c.hidden_size, c.intermediate_size, c.vocab_size
    params: Params = {
        "embed": dense(next(keys), (V, H), H),
        "layers": {
            "attn_norm": jnp.ones((L, H), c.dtype),
            "wq": dense(next(keys), (L, H, c.q_dim), H),
            "wk": dense(next(keys), (L, H, c.kv_dim), H),
            "wv": dense(next(keys), (L, H, c.kv_dim), H),
            "wo": dense(next(keys), (L, c.q_dim, H), c.q_dim),
            "mlp_norm": jnp.ones((L, H), c.dtype),
            "w_gate": dense(next(keys), (L, H, I), H),
            "w_up": dense(next(keys), (L, H, I), H),
            "w_down": dense(next(keys), (L, I, H), I),
        },
        "final_norm": jnp.ones((H,), c.dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (H, V), H)
    return params


# --- KV cache ----------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Decode cache. k/v: [L, B, S_max, KV, D]; lengths: [B] used slots.

    Quantized form (``create(..., quantized=True)``): k/v are int8 with
    per-token per-kv-head symmetric scales k_scale/v_scale [L, B, S_max, KV]
    f32 — halves the cache's HBM bytes, the dominant decode stream once
    contexts grow (weights are already int8 in the flagship config). Dequant
    is fused into the decode attention dots (ops/attention.py
    decode_gqa_attention), so int8 is what actually crosses HBM.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    lengths: jnp.ndarray
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None

    @staticmethod
    def create(cfg: LlamaConfig, batch: int, max_len: int, dtype=None,
               quantized: bool = False) -> "KVCache":
        dtype = dtype or cfg.dtype
        shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        if quantized:
            return KVCache(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                lengths=jnp.zeros((batch,), jnp.int32),
                k_scale=jnp.zeros(shape[:-1], jnp.float32),
                v_scale=jnp.zeros(shape[:-1], jnp.float32),
            )
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((batch,), jnp.int32),
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token per-head symmetric int8 over the last (head_dim) axis:
    x ≈ q * s[..., None]. x: [..., D] -> (int8 [..., D], f32 [...])."""
    q, s = _int8_sym(x, -1)
    return q, jnp.squeeze(s, axis=-1)


@jax.named_scope("kv_insert")
def cache_insert(cache_kv: jnp.ndarray, new_kv: jnp.ndarray, offsets: jnp.ndarray) -> jnp.ndarray:
    """Insert [B, S, ...] at per-batch ``offsets`` into [B, S_max, ...].

    Unrolled over the (small, static) batch: per-row dynamic_update_slice
    stays a real in-place slice write. A vmap'd DUS with per-row offsets
    lowers to a whole-tensor select — measured at several ms/step against a
    large cache — so the loop is the fast path, not a naive one.
    """
    B = cache_kv.shape[0]
    zeros = (0,) * (cache_kv.ndim - 2)
    for b in range(B):
        cache_kv = jax.lax.dynamic_update_slice(
            cache_kv, new_kv[b : b + 1], (b, offsets[b]) + zeros
        )
    return cache_kv


# --- int8 weight quantization ------------------------------------------------
#
# Per-output-channel symmetric int8: w ≈ q * s with q int8, s f32[out].
# Decode on TPU is HBM-bound (every weight byte streams once per step), so
# halving weight bytes ≈ doubles decode throughput; XLA fuses the
# convert(s8→bf16) into the dot and reads one layer of the stack in place, so
# int8 is what crosses HBM, once. That holds for all seven products of a
# layer only while `_qkv` ends q's and k's at their flat results: a product
# with the rotation fused into it has its weights transposed and copied first.
# 8B-class weights (~8 GB int8) fit a single 16 GB v5e chip.


def _int8_sym(w: jnp.ndarray, axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """THE device symmetric-int8 recipe: w ≈ q * s, s keepdims along ``axis``.

    Single source of truth for every on-device quantization (weights via
    :func:`quantize_params`, KV cache via :func:`quantize_kv`); the host copy
    is :func:`quantize_np` and must match exactly."""
    a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(a / 127.0, 1e-12)
    q = jnp.round(w.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s


def quantize_params(params: Params) -> Params:
    """bf16 param pytree -> int8 pytree ({"q": int8, "s": f32} leaves for
    every dense matrix; norms stay as-is). Works with forward/_decode_forward
    transparently via :func:`_mm` / :func:`_embed` / :func:`_logits`."""

    def q(w, axis):
        qw, s = _int8_sym(w, axis)
        return {"q": qw, "s": jnp.squeeze(s, axis=axis)}

    L = params["layers"]
    out: Params = {
        "embed": q(params["embed"], 1),                     # scale per vocab row
        "layers": {
            "attn_norm": L["attn_norm"],
            "wq": q(L["wq"], 1), "wk": q(L["wk"], 1), "wv": q(L["wv"], 1),
            "wo": q(L["wo"], 1),
            "mlp_norm": L["mlp_norm"],
            "w_gate": q(L["w_gate"], 1), "w_up": q(L["w_up"], 1),
            "w_down": q(L["w_down"], 1),
        },
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"], 0)            # scale per vocab col
    return out


def quantize_np(w, axis: int):
    """Per-output-channel symmetric int8 on the host (numpy): w ~= q * s.

    The single source of truth for the numpy quantization recipe — host
    loaders (hf_convert.load_params_quantized, moe's host init) must match
    :func:`quantize_params`'s device recipe exactly, or
    streamed-vs-quantized trees silently diverge.
    """
    import numpy as np

    w = np.asarray(w, np.float32)
    a = np.max(np.abs(w), axis=axis, keepdims=True)
    s = np.maximum(a / 127.0, 1e-12).astype(np.float32)
    q = np.round(w / s).astype(np.int8)
    return {"q": q, "s": np.squeeze(s, axis=axis)}


def init_quantized_params(key: jax.Array, cfg: LlamaConfig,
                          shardings: Any = None) -> Params:
    """Random-init DIRECTLY in int8 on the device(s), leaf by leaf.

    An 8B-class bf16 tree (~16 GB) cannot be materialized on one 16 GB
    chip just to be quantized, and drawing it on the host costs minutes
    of single-threaded numpy before the chip is touched. Each leaf is
    drawn and quantized by its own small jitted program — stacked layer
    weights one layer at a time under ``lax.map``, so the f32 transient
    is one layer of one matrix — and is born in its serving sharding
    (``shardings``: the tree ``parallel.sharding.param_shardings`` gives
    for this function's ``jax.eval_shape``), so nothing is staged through
    one device or the host. The counter-based PRNG draws the same values
    under any sharding: a 1-chip and a 4-chip cell serve the same
    weights."""
    c = cfg
    L, H, I, V = c.num_layers, c.hidden_size, c.intermediate_size, c.vocab_size
    keys = iter(jax.random.split(key, 9))

    def at(*path):
        node = shardings
        for k in path:
            node = None if node is None else node[k]
        return node

    def q(path, shape, fan_in, axis):
        """{"q","s"} for one [.., in, out] matrix; ``axis`` is the
        contracted axis of the unstacked matrix."""

        def one(k, shape2d):
            w = jax.random.normal(k, shape2d, jnp.float32) * fan_in ** -0.5
            qw, s = _int8_sym(w, axis)
            return {"q": qw, "s": jnp.squeeze(s, axis=axis)}

        if len(shape) == 3:
            def fn(k):
                return jax.lax.map(lambda kk: one(kk, shape[1:]),
                                   jax.random.split(k, shape[0]))
        else:
            def fn(k):
                return one(k, shape)
        return jax.jit(fn, out_shardings=at(*path))(next(keys))

    def ones(path, shape):
        return jax.jit(lambda: jnp.ones(shape, c.dtype),
                       out_shardings=at(*path))()

    params: Params = {
        "embed": q(("embed",), (V, H), H, 1),
        "layers": {
            "attn_norm": ones(("layers", "attn_norm"), (L, H)),
            "wq": q(("layers", "wq"), (L, H, c.q_dim), H, 0),
            "wk": q(("layers", "wk"), (L, H, c.kv_dim), H, 0),
            "wv": q(("layers", "wv"), (L, H, c.kv_dim), H, 0),
            "wo": q(("layers", "wo"), (L, c.q_dim, H), c.q_dim, 0),
            "mlp_norm": ones(("layers", "mlp_norm"), (L, H)),
            "w_gate": q(("layers", "w_gate"), (L, H, I), H, 0),
            "w_up": q(("layers", "w_up"), (L, H, I), H, 0),
            "w_down": q(("layers", "w_down"), (L, I, H), I, 0),
        },
        "final_norm": ones(("final_norm",), (H,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = q(("lm_head",), (H, V), H, 0)
    return params


def _is_q(w) -> bool:
    return isinstance(w, dict) and "q" in w


def mm(h: jnp.ndarray, w) -> jnp.ndarray:
    """h @ w for plain or quantized weights (dequant fused into the dot)."""
    if _is_q(w):
        dispatch.note("int8_matmul", "xla")
        return (h @ w["q"].astype(h.dtype)) * w["s"].astype(h.dtype)
    return h @ w


@jax.named_scope("embed")
def embed(params: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    e = params["embed"]
    if _is_q(e):
        rows = jnp.take(e["q"], tokens, axis=0).astype(dtype)
        return rows * jnp.take(e["s"], tokens, axis=0)[..., None].astype(dtype)
    return jnp.take(e, tokens, axis=0).astype(dtype)


@jax.named_scope("lm_head")
def _logits(params: Params, c: LlamaConfig, x: jnp.ndarray) -> jnp.ndarray:
    if c.tie_embeddings:
        e = params["embed"]
        if _is_q(e):
            raw = jnp.einsum("bsh,vh->bsv", x, e["q"].astype(x.dtype))
            return (raw * e["s"].astype(x.dtype)).astype(jnp.float32)
        return jnp.einsum("bsh,vh->bsv", x, e).astype(jnp.float32)
    return _mm(x, params["lm_head"]).astype(jnp.float32)


# The names this file's own forward and older callers use; ``mm``, ``embed``
# and ``cache_insert`` are what the other model families import.
_mm, _embed, _cache_insert = mm, embed, cache_insert


# --- Forward -----------------------------------------------------------------
#
# The pieces of one decoder block, each under a jax.named_scope: the scope
# is metadata on the operations (the compiled code is the same), and it is
# what a device trace can name a fused operation by after a refactor.

def _qkv(x, w: dict, c: LlamaConfig, positions):
    """x [B, S, H] -> rotated q [B, S, NH, D] and k, v [B, S, KV, D]."""
    B, S = x.shape[:2]
    with jax.named_scope("attn_norm"):
        h = rms_norm(x, w["attn_norm"], c.rms_norm_eps)
    with jax.named_scope("qkv"):
        # The three products END at their flat [B, S, out] results. Without
        # the barrier the TPU compiler fuses the reshape to heads and the
        # rotation's float32 halves into q's and k's products, and that form
        # of a product wants its weight with the contracted axis minor: an
        # int8 decode chunk then transposes the whole wq and wk stacks once a
        # call (copy.105 / copy.104, 0.67 GB of temporaries) and copies one
        # layer of each to VMEM every layer of every step, and a prefill
        # slices and transposes a layer of each. Behind the barrier all seven
        # products of a layer read their stacks in place
        # (tests/test_chip_compile.py,
        # test_a_dense_cells_programs_make_no_value_of_a_weights_size).
        q, k, v = jax.lax.optimization_barrier((
            _mm(h, w["wq"]), _mm(h, w["wk"]), _mm(h, w["wv"])))
        q = q.reshape(B, S, c.num_heads, c.head_dim)
        k = k.reshape(B, S, c.num_kv_heads, c.head_dim)
        v = v.reshape(B, S, c.num_kv_heads, c.head_dim)
    with jax.named_scope("rope"):
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
    return q, k, v


def _wo(x, attn, w: dict, c: LlamaConfig):
    """The attention block's output projection and residual."""
    B, S = x.shape[:2]
    with jax.named_scope("wo"):
        return x + _mm(attn.reshape(B, S, c.q_dim), w["wo"])


def _mlp(x, w: dict, c: LlamaConfig):
    """The SwiGLU block and its residual."""
    with jax.named_scope("mlp_norm"):
        h = rms_norm(x, w["mlp_norm"], c.rms_norm_eps)
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(
            _mm(h, w["w_gate"]).astype(jnp.float32)).astype(c.dtype)
        up = _mm(h, w["w_up"])
        return x + _mm(gate * up, w["w_down"])


def transformer_block(
    x: jnp.ndarray,
    w: dict,
    cfg: LlamaConfig,
    positions: jnp.ndarray,
    attn_impl: str = "auto",
) -> jnp.ndarray:
    """One no-cache decoder block (attention + SwiGLU residual) over
    [B, S, H]. Identical math to ``forward``'s cacheless layer step; exposed
    standalone for the pipeline-parallel path (parallel/pipeline.py), whose
    per-stage scan runs blocks outside forward's whole-model scan."""
    c = cfg
    q, k, v = _qkv(x, w, c, positions)
    attn = gqa_attention(
        q, k, v, q_positions=positions, kv_positions=positions, impl=attn_impl
    )
    return _mlp(_wo(x, attn, w, c), w, c)


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache | None = None,
    attn_impl: str = "auto",
    logit_positions: jnp.ndarray | None = None,
    active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache | None]:
    """Run the decoder.

    Args:
      params: pytree from :func:`init_params`.
      tokens: [B, S] int32 token ids.
      positions: [B, S] absolute positions of those tokens.
      cache: optional KVCache; when given, new K/V are written at each
        sequence's current length and attention runs against the cache.
        ``positions`` must equal ``cache.lengths[:, None] + arange(S)``.
      logit_positions: optional [B] int32 sequence indices; when given, the
        LM head runs at ONLY those positions and logits come back [B, 1, V].
        Prefill needs one next-token distribution, not S_bucket of them —
        at 8B shapes the full head is an S×H×128k matmul plus a [S, 128k]
        f32 tensor, bigger than the rest of the prefill combined.
      active: optional [B] bool, single-token decode only: the slots whose
        output the caller keeps. The others read no cache row.

    Returns:
      (logits [B, S, V] float32 — [B, 1, V] with ``logit_positions`` —
      and the updated cache or None).
    """
    c = cfg
    B, S = tokens.shape
    x = _embed(params, tokens, c.dtype)  # [B, S, H]

    # The fused decode path implements its own (reference-equivalent) masked
    # attention; honor an explicit request for a specific impl by falling
    # through to the generic path instead of silently ignoring it.
    # (logit_positions is moot at S == 1: there is only one position.)
    if cache is not None and S == 1 and attn_impl in ("auto", "reference"):
        return _decode_forward(params, c, x, positions, cache, B, active)

    offsets = cache.lengths if cache is not None else None

    def layer_step(x, layer):
        w, layer_cache = layer
        q, k, v = _qkv(x, w, c, positions)

        if layer_cache is not None:
            ck, cv, cks, cvs = layer_cache
            if cks is not None:
                # Quantized cache, generic (multi-token) path: quantize the
                # new K/V in, then dequantize the whole layer cache for the
                # attention. Prefill is compute-bound, so the materialized
                # dequant is fine here; the HBM-bound decode path fuses it
                # (_decode_forward / decode_gqa_attention).
                qk, sk = quantize_kv(k)
                qv, sv = quantize_kv(v)
                ck = _cache_insert(ck, qk, offsets)
                cv = _cache_insert(cv, qv, offsets)
                cks = _cache_insert(cks, sk, offsets)
                cvs = _cache_insert(cvs, sv, offsets)
                # Dequantize in f32 and cast the PRODUCT down: scaling the
                # f32 scales to bf16 first would double-round, and the fused
                # decode path applies scales in f32 — the two paths must
                # agree numerically (ADVICE r4).
                ak = (ck.astype(jnp.float32)
                      * cks[..., None].astype(jnp.float32)).astype(c.dtype)
                av = (cv.astype(jnp.float32)
                      * cvs[..., None].astype(jnp.float32)).astype(c.dtype)
            else:
                ck = _cache_insert(ck, k, offsets)
                cv = _cache_insert(cv, v, offsets)
                ak, av = ck, cv
            kv_positions = jnp.broadcast_to(
                jnp.arange(ck.shape[1], dtype=jnp.int32)[None, :], (B, ck.shape[1])
            )
            kv_length = offsets + S
            attn = gqa_attention(
                q, ak, av,
                q_positions=positions, kv_positions=kv_positions,
                kv_length=kv_length, impl=attn_impl,
            )
            new_layer_cache = (ck, cv, cks, cvs)
        else:
            attn = gqa_attention(
                q, k, v,
                q_positions=positions, kv_positions=positions, impl=attn_impl,
            )
            new_layer_cache = None

        return _mlp(_wo(x, attn, w, c), w, c), new_layer_cache

    layer_ws = params["layers"]
    if cache is not None:
        x, (new_k, new_v, new_ks, new_vs) = jax.lax.scan(
            lambda carry, layer: layer_step(carry, (layer[0], layer[1:])),
            x,
            (layer_ws, cache.k, cache.v, cache.k_scale, cache.v_scale),
        )
        new_cache = KVCache(k=new_k, v=new_v, lengths=cache.lengths + S,
                            k_scale=new_ks, v_scale=new_vs)
    else:
        x, _ = jax.lax.scan(
            lambda carry, w: layer_step(carry, (w, None)), x, layer_ws
        )
        new_cache = None

    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    if logit_positions is not None:
        x = jnp.take_along_axis(x, logit_positions[:, None, None], axis=1)
    return _logits(params, c, x), new_cache


def _decode_forward(
    params: Params,
    c: LlamaConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache,
    B: int,
    active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Single-token decode, HBM-optimal.

    The generic path writes each layer's K/V into the cache BEFORE attending
    and re-stacks the full cache as scan outputs — two whole-cache copies per
    step. Here the layer scan reads the cache as a read-only input
    (append-free attention scores the new token separately), emits only the
    tiny per-layer new K/V, and the cache is updated once per step with
    per-slot in-place slice writes. Cache bytes stream through HBM exactly
    once per step — and for a quantized cache those bytes are int8, with
    dequant fused into the attention dots. A slot that is not ``active``
    reads no row at all (its output is the caller's to drop).
    """
    from kukeon_tpu.ops.attention import decode_gqa_attention

    offsets = cache.lengths
    reads = offsets if active is None else jnp.where(active, offsets, 0)

    def layer_step(x, layer):
        w, i = layer
        q, k, v = _qkv(x, w, c, positions)
        attn = decode_gqa_attention(
            q, k, v, cache.k, cache.v, i, reads, k_scale=cache.k_scale,
            v_scale=cache.v_scale)
        return _mlp(_wo(x, attn, w, c), w, c), (k, v)

    # The stacks are read at the layer's index, not scanned over: a kernel
    # takes the whole stack as its operand and reads the layer in place.
    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], jnp.arange(cache.k.shape[0])))
    # new_k/new_v: [L, B, 1, KV, D] — one in-place slice write per slot
    # covering every layer at once (layers share the slot's offset).
    with jax.named_scope("kv_insert"):
        k_upd, v_upd = cache.k, cache.v
        ks_upd, vs_upd = cache.k_scale, cache.v_scale
        if cache.quantized:
            new_k, new_ks = quantize_kv(new_k)       # [L, B, 1, KV, D] / [L, B, 1, KV]
            new_v, new_vs = quantize_kv(new_v)
        for b in range(B):
            start = (0, b, offsets[b], 0, 0)
            k_upd = jax.lax.dynamic_update_slice(k_upd, new_k[:, b : b + 1], start)
            v_upd = jax.lax.dynamic_update_slice(v_upd, new_v[:, b : b + 1], start)
            if cache.quantized:
                ks_upd = jax.lax.dynamic_update_slice(
                    ks_upd, new_ks[:, b : b + 1], start[:-1])
                vs_upd = jax.lax.dynamic_update_slice(
                    vs_upd, new_vs[:, b : b + 1], start[:-1])
    new_cache = KVCache(k=k_upd, v=v_upd, lengths=cache.lengths + 1,
                        k_scale=ks_upd, v_scale=vs_upd)

    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return _logits(params, c, x), new_cache
