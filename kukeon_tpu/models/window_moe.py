"""Window-and-full attention decoder with an expert layer (the ``afmoe`` block
of arcee-ai's Trinity models), functional JAX.

What ``models/llama.py`` has none of:

- **four norms a layer** ("sandwich"): ``x += N2(Attn(N1 x))``, ``x += N4(MLP(N3
  x))``; a scaled embedding (``x0 = E[tokens] * sqrt(hidden)``);
- **gated attention with per-head QK-norm**: ``q``, ``k`` are RMS-normed over
  the head, the attention output is multiplied by ``sigmoid(h Wg)`` element by
  element before ``Wo``;
- **window and full layers side by side**: a ``sliding_attention`` layer
  rotates ``q``/``k`` (RoPE) and attends to the last ``sliding_window``
  positions; a ``full_attention`` layer uses no positional encoding at all and
  attends to everything before it;
- **leading dense layers, then expert layers** (``models/expert_layer.py``:
  sigmoid scores, a selection bias, one shared expert, and the experts this
  chip HOLDS of the ``num_experts`` the router scores).

Layout: the leading layers are unrolled (``params["head"]`` is a list: the
``num_dense_layers`` dense ones and any expert layers before the first whole
period); the rest come in whole periods of ``layer_types`` (window, window,
window, full) and run under ONE ``lax.scan`` over periods:
``params["period"][j]`` stacks position ``j`` of every period on axis 0, so
depth costs no compile time.

Two forwards, for the serving engine's per-kind cache (``models/kv_kinds.py``):
``prefill`` runs a whole prompt without a cache, attention in query blocks (a
window layer's block reads only its band of keys, so an 8192-token prompt
never builds an [S, S] score matrix), and returns every layer's K/V block
(``{"k", "v"}``, the block ``kv_kinds.insert`` takes);
``decode`` runs one token a slot against the held stacks (a ring of
``sliding_window`` rows for window layers, ``S_max`` rows for full ones). Keys
are stored rotated, so ring order does not matter.

Not here, and refused at boot rather than served wrongly
(``models/families.py``): int8 weights or KV, paged KV, a prefix store,
a mesh of more than one chip, multi-token prediction, training.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from kukeon_tpu.models import drawn, kv_kinds
from kukeon_tpu.models.expert_layer import (
    COUNTS, NO_COUNTS, expert_layer_counts, swiglu)
from kukeon_tpu.models.llama import embed, mm
from kukeon_tpu.ops.attention import blocked_attention, decode_gqa_attention
from kukeon_tpu.ops.norms import rms_norm
from kukeon_tpu.ops.rope import apply_rope

Params = dict[str, Any]
SLIDING, FULL = "sliding_attention", "full_attention"
# Device-summed counters a forward returns beside its logits, in this order.
COUNTERS = ("kukeon_moe_routed_total", *COUNTS)
PREFILL_BLOCK = 512     # query rows a prefill attends at once (a bucket's, if fewer)


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the dense layers' SwiGLU
    moe_intermediate_size: int = 3072       # one expert's, and the shared one's
    layer_types: tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 15
    num_dense_layers: int = 6
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256                  # the router's width
    experts_per_token: int = 4
    experts_held: tuple[int, int] = (0, 256)    # (first, count) on this chip
    sliding_window: int = 4096
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    route_scale: float = 2.448
    route_norm: bool = True
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types, p = self.layer_types[self.num_unrolled:], self.period
        if (not types or types != p * (len(types) // len(p))
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(
                "layer_types must end in whole periods of window layers "
                f"closed by a full one; got {self.layer_types}")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> tuple[str, ...]:
        """The expert layers' repeating pattern, read off the model's end: from
        after the last full layer but one to the last layer."""
        types = self.layer_types[self.num_dense_layers:]
        fulls = [i for i, t in enumerate(types[:-1]) if t == FULL]
        return types[fulls[-1] + 1:] if fulls else types

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.num_dense_layers) // len(self.period)

    @property
    def num_unrolled(self) -> int:
        """The dense layers and the expert layers before the first whole
        period (the published model's layers 6 and 7)."""
        return self.num_layers - self.num_periods * len(self.period)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def cache_kinds(self, max_seq_len: int) -> tuple[kv_kinds.CacheKind, ...]:
        """What each layer holds for a slot: a ring of the window's rows, or
        every row."""
        def of(kind):
            return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

        kinds = []
        if of(SLIDING):
            kinds.append(kv_kinds.CacheKind(
                "window", of(SLIDING), min(self.sliding_window, max_seq_len),
                ring=True))
        if of(FULL):
            kinds.append(kv_kinds.CacheKind("full", of(FULL), max_seq_len))
        return tuple(kinds)


def trinity_large_preview() -> WindowMoEConfig:
    """arcee-ai/Trinity-Large-Preview as published (400B-A13B)."""
    return WindowMoEConfig()


def window_moe_tiny() -> WindowMoEConfig:
    """Test size: one dense layer and two periods, a window of 8 rows, this
    chip holding 4 of 16 experts."""
    return WindowMoEConfig(
        vocab_size=384, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48,
        layer_types=(SLIDING,) + ((SLIDING,) * 3 + (FULL,)) * 2,
        num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=16, experts_per_token=4, experts_held=(4, 4),
        sliding_window=8, max_seq_len=64, dtype=jnp.float32)


# --- Init --------------------------------------------------------------------
#
# The weights ARE their recipe (``models/drawn.py``): this family's table, and
# ``benchmark/reference/window_moe.py`` draws the same values without
# importing this file (tests/bench pins the two).

LEAVES = ("embed", "lm_head", "final_norm", "norm1", "norm2", "norm3",
          "norm4", "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo",
          "w_gate", "w_up", "w_down", "router", "bias", "s_gate", "s_up",
          "s_down", "e_gate", "e_up", "e_down")
BIAS_STD = 0.02     # a tenth of the sigmoid scores' spread (0.21)

_key = functools.partial(drawn.leaf_key, LEAVES)


def _layer_leaves(cfg: WindowMoEConfig, dense: bool) -> dict:
    """name -> (kind, shape, fan_in) of one layer's leaves."""
    c = cfg
    H, D, Im = c.hidden_size, c.head_dim, c.moe_intermediate_size
    out = {f"norm{i}": ("gain", (H,), 0) for i in (1, 2, 3, 4)}
    out.update({
        "q_norm": ("gain", (D,), 0), "k_norm": ("gain", (D,), 0),
        "wq": ("matrix", (H, c.q_dim), H), "wk": ("matrix", (H, c.kv_dim), H),
        "wv": ("matrix", (H, c.kv_dim), H), "wg": ("matrix", (H, c.q_dim), H),
        "wo": ("matrix", (c.q_dim, H), c.q_dim)})
    if dense:
        I = c.intermediate_size
        out.update({"w_gate": ("matrix", (H, I), H),
                    "w_up": ("matrix", (H, I), H),
                    "w_down": ("matrix", (I, H), I)})
    else:
        out.update({
            "router": ("router", (H, c.num_experts), H),
            "bias": ("bias", (c.num_experts,), 0),
            "s_gate": ("matrix", (H, Im), H), "s_up": ("matrix", (H, Im), H),
            "s_down": ("matrix", (Im, H), Im),
            "e_gate": ("experts", (H, Im), H), "e_up": ("experts", (H, Im), H),
            "e_down": ("experts", (Im, H), Im)})
    return out


def _draw(key, cfg, name, kind, shape, fan_in, layer):
    if kind == "bias":
        return BIAS_STD * jax.random.normal(
            _key(key, name, layer), shape, jnp.float32)
    return drawn.draw(LEAVES, key, cfg, name, kind, shape, fan_in, layer)


def _draw_params(key: jax.Array, cfg: WindowMoEConfig) -> Params:
    c = cfg
    Ld, U, P, p = (c.num_dense_layers, c.num_unrolled, c.num_periods,
                   len(c.period))
    H, V = c.hidden_size, c.vocab_size
    return {
        "embed": drawn.matrix(_key(key, "embed"), (V, H), H, c.dtype),
        "lm_head": drawn.matrix(_key(key, "lm_head"), (H, V), H, c.dtype),
        "final_norm": drawn.gain(_key(key, "final_norm"), (H,), c.dtype),
        "head": [
            {name: _draw(key, c, name, *spec, i)
             for name, spec in _layer_leaves(c, i < Ld).items()}
            for i in range(U)],
        "period": [
            {name: jax.lax.map(
                lambda layer, n=name, s=spec: _draw(key, c, n, *s, layer),
                U + j + p * jnp.arange(P))
             for name, spec in _layer_leaves(c, False).items()}
            for j in range(p)],
    }


init_params = functools.partial(drawn.init, _draw_params)
param_specs = drawn.whole


# --- The block ---------------------------------------------------------------

def _qkvg(x, w: dict, c: WindowMoEConfig, positions, rotary: bool):
    """x [B, S, H] -> normed (and on a window layer rotated) q [B, S, NH, D],
    k, v [B, S, KV, D], and the gate's pre-activation [B, S, NH * D]."""
    B, S = x.shape[:2]
    h = rms_norm(x, w["norm1"], c.rms_norm_eps)
    q = mm(h, w["wq"]).reshape(B, S, c.num_heads, c.head_dim)
    k = mm(h, w["wk"]).reshape(B, S, c.num_kv_heads, c.head_dim)
    v = mm(h, w["wv"]).reshape(B, S, c.num_kv_heads, c.head_dim)
    gate = mm(h, w["wg"])
    q = rms_norm(q, w["q_norm"], c.rms_norm_eps)
    k = rms_norm(k, w["k_norm"], c.rms_norm_eps)
    if rotary:
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
    return q, k, v, gate


def _attn_out(x, attn, gate, w: dict, c: WindowMoEConfig):
    B, S = x.shape[:2]
    a = attn.reshape(B, S, c.q_dim) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(attn.dtype)
    return x + rms_norm(mm(a, w["wo"]), w["norm2"], c.rms_norm_eps)


def _mlp(x, w: dict, c: WindowMoEConfig, counted):
    """The dense SwiGLU or the expert layer, by the leaves the layer has;
    returns (x', the expert layer's COUNTS)."""
    h = rms_norm(x, w["norm3"], c.rms_norm_eps)
    if "router" in w:
        m, tally = expert_layer_counts(
            h, w, experts_per_token=c.experts_per_token,
            experts_held=c.experts_held, route_norm=c.route_norm,
            route_scale=c.route_scale, counted=counted)
    else:
        m, tally = swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), NO_COUNTS
    return x + rms_norm(m, w["norm4"], c.rms_norm_eps), tally


def _scope(layer_type: str) -> str:
    return "window_attention" if layer_type == SLIDING else "full_attention"


def _embed_scaled(params, c: WindowMoEConfig, tokens):
    return embed(params, tokens, c.dtype) * jnp.asarray(
        c.hidden_size ** 0.5, c.dtype)


def _head(params, c: WindowMoEConfig, x):
    with jax.named_scope("lm_head"):
        return mm(rms_norm(x, params["final_norm"], c.rms_norm_eps),
                  params["lm_head"]).astype(jnp.float32)


def _counters(c: WindowMoEConfig, counted, tally) -> jnp.ndarray:
    """COUNTERS for one forward: every counted token makes
    ``experts_per_token`` choices in each expert layer."""
    routed = (jnp.sum(counted, dtype=jnp.int32) * c.experts_per_token
              * (c.num_layers - c.num_dense_layers))
    return jnp.stack([routed, *tally])


def _through_layers(params: Params, c: WindowMoEConfig, x, layer):
    """x through the unrolled head and ONE scan over the periods.
    ``layer(x, w, layer_type, number) -> (x', k, v, tally)``; ``number`` is
    the layer's place in the model (traced inside the scan). Returns (x, K, V
    stacked over the layers in their order, the expert layers' COUNTS)."""
    ks, vs = [], []
    tally = NO_COUNTS
    for number, (w, t) in enumerate(zip(params["head"], c.layer_types)):
        x, k, v, h = layer(x, w, t, number)
        tally = tally + h
        ks.append(k)
        vs.append(v)
    U, p = c.num_unrolled, len(c.period)

    def period(carry, xs):
        x, tally = carry
        ws, i = xs
        out_k, out_v = [], []
        for j, (w, t) in enumerate(zip(ws, c.period)):
            x, k, v, h = layer(x, w, t, U + i * p + j)
            tally = tally + h
            out_k.append(k)
            out_v.append(v)
        return (x, tally), (jnp.stack(out_k), jnp.stack(out_v))

    (x, tally), (pk, pv) = jax.lax.scan(
        period, (x, tally),
        (tuple(params["period"]), jnp.arange(c.num_periods)))
    # [P, p, ...] -> the periods' layers in their order
    ks.extend(pk.reshape(-1, *pk.shape[2:]))
    vs.extend(pv.reshape(-1, *pv.shape[2:]))
    return x, jnp.stack(ks), jnp.stack(vs), tally


def prefill(params: Params, cfg: WindowMoEConfig, tokens: jnp.ndarray,
            length) -> tuple[jnp.ndarray, dict, jnp.ndarray]:
    """tokens [1, S] (``length`` of them real) -> (float32 logits [V] of the
    last real position, the block ``{"k", "v"}`` [L, 1, S, KV, D] with window
    layers' keys rotated, COUNTERS)."""
    c = cfg
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    counted = positions < length

    def layer(x, w, layer_type, _number):
        sliding = layer_type == SLIDING
        q, k, v, gate = _qkvg(x, w, c, positions, rotary=sliding)
        with jax.named_scope(_scope(layer_type)):
            attn = blocked_attention(
                q, k, v, c.sliding_window if sliding else None,
                PREFILL_BLOCK)
        x, tally = _mlp(_attn_out(x, attn, gate, w, c), w, c, counted)
        return x, k, v, tally

    x, ks, vs, tally = _through_layers(
        params, c, _embed_scaled(params, c, tokens), layer)
    last = jax.lax.dynamic_index_in_dim(x[0], length - 1, keepdims=True)
    return (_head(params, c, last)[0], {"k": ks, "v": vs},
            _counters(c, counted, tally))


def decode(params: Params, cfg: WindowMoEConfig, tokens: jnp.ndarray,
           cache: kv_kinds.LayeredKV, kinds, active: jnp.ndarray):
    """One token a slot against the VIEW of the held cache (``kv_kinds``):
    tokens [B] at positions ``cache.lengths`` -> (float32 logits [B, V], this
    step's rows ``{"k", "v"}`` [L, B, 1, KV, D], COUNTERS over the ``active``
    slots). The cache is read, never written: the caller appends."""
    c = cfg
    lengths = cache.lengths
    positions = lengths[:, None]
    counted = active[:, None]
    # a layer's kind follows from its type; its place in that kind's stack
    # is looked up by its number
    kind_of = {SLIDING if kd.ring else FULL: i for i, kd in enumerate(kinds)}
    index_of = [0] * c.num_layers
    for kd in kinds:
        for n, number in enumerate(kd.layers):
            index_of[number] = n
    index_of = jnp.asarray(index_of, jnp.int32)
    # the rows each kind reads, as numbers; a slot that is not active reads
    # none (its output is dropped: the engine keeps such a slot's token)
    reads = [(jnp.where(active, count, 0), skip) for count, skip
             in (kv_kinds.valid(kd, lengths) for kd in kinds)]

    def layer(x, w, layer_type, number):
        kind = kind_of[layer_type]
        q, k, v, gate = _qkvg(x, w, c, positions, rotary=layer_type == SLIDING)
        count, skip = reads[kind]
        with jax.named_scope(_scope(layer_type)):
            attn = decode_gqa_attention(
                q, k, v, cache.held[kind]["k"], cache.held[kind]["v"],
                index_of[number], count, skip=skip)
        x, tally = _mlp(_attn_out(x, attn, gate, w, c), w, c, counted)
        return x, k, v, tally

    x, ks, vs, tally = _through_layers(
        params, c, _embed_scaled(params, c, tokens[:, None]), layer)
    return (_head(params, c, x)[:, 0], {"k": ks, "v": vs},
            _counters(c, counted, tally))
