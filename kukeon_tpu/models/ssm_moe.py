"""Mamba-2 mixers with an attention layer among them and an expert layer in
EVERY layer (the ``granitemoehybrid`` block of IBM's Granite 4.0-H models),
functional JAX.

    x0 = embedding_multiplier * E[tokens]
    x  = x + r * Mix_l(N1_l x);   x = x + r * (MoE_l(N2_l x) + Shared_l(N2_l x))
    logits = (N_f(x) E^T) / logits_scaling              r = residual_multiplier

What it shares and with whom: the layer pattern as data, the position-free
attention layer, the convolution's tap, the state read at ``length`` and the
recipe's draws are ``models/ssm_hybrid.py``'s (imported, not copied); the expert
layer is ``models/expert_layer.py``'s with the router this family has
(``softmax_selected``: a softmax over the top-k LOGITS, no bias, no scale) and
the experts this chip HOLDS of the ``num_experts`` the router scores; a decode
step's scan state moves in place in the held stack through
``ops/selective_scan.py`` ``update_held`` (the ACTIVE slots only). What no
other family has:

- **a Mamba-2 mixer**: ONE input projection into the gate ``z`` [I], the
  convolved ``x | B | C`` [I + 2 N] and a step size a HEAD [heads]; a decay
  that is one scalar a head, ``A[h]``; ``B`` and ``C`` shared by all heads
  (one group); a gated RMSNorm over all ``I`` channels, the gate BEFORE the
  norm: ``out = (N(y * silu(z))) W_out``. The prefill's scan is the chunked
  matrix form (``ops/ssd_scan.py``, on the MXU);
- **the four multipliers**: the embedding's, the residual branches', the
  attention scores' (``attention_multiplier`` in place of ``D ** -0.5``; the
  attention entry points take it as ``scale`` and multiply the float32 scores
  by it, as the reference does) and the logits' divisor.

**The held state** (``cache_kinds``): a mixer's scan state is float32 and
STATE-major, ``ssm [mixers, B, N, I]`` with channel ``i = head * P + p`` on the
lanes: at the published sizes 128 x 8192 x 4 B = 4 MiB a slot and mixer,
exactly (no lane is padding: an ``[.., P, N]`` or ``[.., N]``-minor layout at
64 channels a head would be), 37.7 MB a slot over 9 mixers. Laid so, a decode
step IS Mamba-1's recurrence with ``A`` and the step size constant over a
head's ``P`` channels, which is why ``update_held`` serves both. The
convolution's tail is ``conv [mixers, d_conv - 1, B, I + 2 N]`` in the
activations' dtype (x, B and C are convolved together), and the attention
layers hold full stacks of rows.

Layout of the parameters: ``params["layers"]`` is a list, one dict of leaves a
layer, and the forwards UNROLL the layers (as ``window_moe``'s head and
``sparse_latent_moe`` do). ``ssm_hybrid``'s scan over the mixers reads a
layer's weights out of a stack by a traced index, and compiled for the chip
that slice is COPIED on its way into a product wherever the compiler wants
another tiling, and always on its way into ``ragged-dot``: at this model's
sizes 0.9 GB a mixer and decode step (three expert stacks of 226 MB, ``w_in``
136 MB, ``w_out`` 67 MB; compile-only, PR 43). A layer's own arrays are read
where they lie. The published ``in_proj`` is held as its two column blocks,
``w_in`` [H, 2 I + 2 N] and ``w_dt`` [H, heads], so that the step size is
projected in float32.

Not here, and refused at boot rather than served wrongly
(``models/families.py``): int8 weights or KV, paged KV, a prefix store (it
would have to snapshot state), a mesh of more than one chip, a checkpoint,
training.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from kukeon_tpu.models import drawn, kv_kinds
from kukeon_tpu.models import ssm_hybrid as sh
from kukeon_tpu.models.expert_layer import (
    COUNTS, NO_COUNTS, SOFTMAX_SELECTED, expert_layer_counts)
from kukeon_tpu.models.llama import embed, mm
from kukeon_tpu.ops import selective_scan as ss
from kukeon_tpu.ops import ssd_scan as ssd
from kukeon_tpu.ops.attention import blocked_attention, decode_gqa_attention
from kukeon_tpu.ops.norms import rms_norm

Params = dict[str, Any]
# Device-summed counters a forward returns beside its logits, in this order.
COUNTERS = ("kukeon_moe_routed_total", *COUNTS,
            "kukeon_moe_routed_tokens_total")
PREFILL_BLOCK = 512     # query rows a prefill attends at once (a bucket's, if fewer)
MLP_ROWS = 2048         # rows a prefill takes through an expert layer at once
STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmMoEConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    moe_intermediate_size: int = 768        # one expert's (intermediate_size)
    shared_intermediate_size: int = 1536
    num_layers: int = 40
    attn_layer_period: int = 10
    attn_layer_offset: int = 5
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256                        # mamba_chunk_size
    num_experts: int = 72                   # the router's width
    experts_per_token: int = 10
    experts_held: tuple[int, int] = (0, 72)     # (first, count) on this chip
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.num_layers % self.attn_layer_period
                or not 0 <= self.attn_layer_offset < self.attn_layer_period):
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of "
                f"{self.attn_layer_period} with the attention layer at "
                f"{self.attn_layer_offset}")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")

    # the pattern, as ``ssm_hybrid`` reads it off a config
    num_periods = sh.SsmHybridConfig.num_periods
    runs = sh.SsmHybridConfig.runs
    num_mixers = sh.SsmHybridConfig.num_mixers
    layer_types = sh.SsmHybridConfig.layer_types
    q_dim = sh.SsmHybridConfig.q_dim
    kv_dim = sh.SsmHybridConfig.kv_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """x, B and C, convolved together (one group)."""
        return self.d_inner + 2 * self.d_state

    def cache_kinds(self, max_seq_len: int) -> tuple[kv_kinds.CacheKind, ...]:
        """What a slot holds: every mixer's convolution tail and scan state
        (the module's docstring has the layout and its bytes) and every row
        of the attention layers."""
        M = self.num_mixers
        types = self.layer_types
        return (
            kv_kinds.CacheKind(
                "state", tuple(i for i, t in enumerate(types) if t == "mamba"),
                state=(("conv", (M, self.d_conv - 1, None, self.conv_dim),
                        self.dtype),
                       ("ssm", (M, None, self.d_state, self.d_inner),
                        STATE_DTYPE))),
            kv_kinds.CacheKind("full", tuple(range(self.num_periods)),
                               max_seq_len))


def granite_4_h_small() -> SsmMoEConfig:
    """ibm-granite/granite-4.0-h-small as published (32B-A9B)."""
    return SsmMoEConfig()


def ssm_moe_tiny() -> SsmMoEConfig:
    """Test size: two periods of (mixer, attention, mixer, mixer), 4 heads of
    8 channels x 16 states, this chip holding 4 of 8 experts, top-2."""
    return SsmMoEConfig(
        vocab_size=384, hidden_size=64, moe_intermediate_size=48,
        shared_intermediate_size=96, num_layers=8, attn_layer_period=4,
        attn_layer_offset=1, num_heads=4, num_kv_heads=2, head_dim=16,
        mamba_heads=4, mamba_head_dim=8, d_state=16, d_conv=4, chunk=8,
        num_experts=8, experts_per_token=2, experts_held=(4, 4),
        attention_multiplier=1 / 16, max_seq_len=128, dtype=jnp.float32)


# --- Init --------------------------------------------------------------------
#
# The weights ARE their recipe (``models/drawn.py``): this family's table, and
# ``benchmark/reference/ssm_moe.py`` draws the same values without importing
# this file (tests/bench pins the two). The scan's leaves leave the state a
# long memory, or a check against the reference could not see a lost state:
# A uniform in [1, 16] a head, D = 1, the step size's bias the inverse
# softplus of a log-uniform draw in [1e-3, 1e-1], its projection small: a
# head's decay a step runs from 0.9999 to 0.2; and in_proj's B and C columns
# are drawn TWICE as wide, or the state would be a third of what the mixer's
# norm sees beside the skip ``D x`` and a lost state would move the logits
# less than bfloat16's rounding does (at unit width the part of ``S C`` that
# turns on the inputs reads 0.19 against the skip's 0.61, at twice 1.35:
# PERF.md section 6, PR 43). Under the published multipliers
# a matrix of deviation fan_in ** -0.5 would leave the attention scores at a
# deviation of 0.09 (q . k / 128), the token's own embedding twelve times the
# size of a branch's output in the residual (and, the head being tied, its own
# logit twelve deviations above the rest: every answer one token, repeated)
# and the logits at 1 / 16: wq and wk are drawn 128 ** 0.25 times wider
# (scores of deviation 1, as D ** -0.5 gives unit q and k), the embedding
# 1 / embedding_multiplier as wide (x0 a row of deviation H ** -0.5) and the
# final norm's gain logits_scaling * embedding_multiplier times larger (logits
# of deviation 1.0 after the division, which the benchmark's check assumes).

LEAVES = ("embed", "final_norm", "norm1", "norm2", "router", "s_gate", "s_up",
          "s_down", "e_gate", "e_up", "e_down", "wq", "wk", "wv", "wo",
          "w_in", "w_dt", "conv_w", "conv_b", "b_dt", "a_log", "mixer_norm",
          "w_out")
CONV_BIAS_STD = 0.1
DT_SCALE = 0.1
BC_SCALE = 2.0      # a power of two: exact in every dtype
A_MIN, A_MAX = 1.0, 16.0


_key = functools.partial(drawn.leaf_key, LEAVES)


def _layer_leaves(c: SsmMoEConfig, mixer: bool) -> dict:
    """name -> (kind, shape, fan_in) of one layer's drawn leaves."""
    H, Im, Is = (c.hidden_size, c.moe_intermediate_size,
                 c.shared_intermediate_size)
    I, C, MH = c.d_inner, c.conv_dim, c.mamba_heads
    out = {"norm1": ("gain", (H,), 0), "norm2": ("gain", (H,), 0),
           "router": ("router", (H, c.num_experts), H),
           "s_gate": ("matrix", (H, Is), H), "s_up": ("matrix", (H, Is), H),
           "s_down": ("matrix", (Is, H), Is),
           "e_gate": ("experts", (H, Im), H), "e_up": ("experts", (H, Im), H),
           "e_down": ("experts", (Im, H), Im)}
    if not mixer:
        out.update({"wq": ("wide", (H, c.q_dim), H),
                    "wk": ("wide", (H, c.kv_dim), H),
                    "wv": ("matrix", (H, c.kv_dim), H),
                    "wo": ("matrix", (c.q_dim, H), c.q_dim)})
        return out
    out.update({
        "w_in": ("in_proj", (H, I + C), H),         # z | x B C
        "w_dt": ("dt", (H, MH), H),
        # drawn [C, d_conv] as published and held taps-major, as the tail is
        "conv_w": ("taps", (C, c.d_conv), c.d_conv),
        "conv_b": ("conv_bias", (C,), 0),
        "b_dt": ("dt_bias", (MH,), 0), "a_log": ("a_log", (MH,), 0),
        "mixer_norm": ("gain", (I,), 0),
        "w_out": ("matrix", (I, H), I)})
    return out


def _draw(key, c: SsmMoEConfig, name, kind, shape, fan_in, layer):
    k = _key(key, name, layer)
    if kind == "wide":
        return drawn.matrix(k, shape, fan_in, c.dtype, c.head_dim ** 0.25)
    if kind == "in_proj":
        wide = jnp.arange(shape[1]) >= 2 * c.d_inner        # B and C
        return drawn.matrix(k, shape, fan_in, c.dtype) * jnp.where(
            wide, BC_SCALE, 1.0).astype(c.dtype)
    if kind == "taps":
        return drawn.matrix(k, shape, fan_in, c.dtype).T
    if kind == "conv_bias":
        return (CONV_BIAS_STD * jax.random.normal(k, shape, jnp.float32)
                ).astype(c.dtype)
    if kind == "dt":
        return drawn.matrix(k, shape, fan_in, c.dtype, DT_SCALE)
    if kind == "dt_bias":
        return sh.dt_bias(k, shape)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, A_MIN, A_MAX))
    return drawn.draw(LEAVES, key, c, name, kind, shape, fan_in, layer)


def _draw_params(key: jax.Array, c: SsmMoEConfig) -> Params:
    H = c.hidden_size

    def layer(number: int, mixer: bool) -> dict:
        w = {name: _draw(key, c, name, *spec, number)
             for name, spec in _layer_leaves(c, mixer).items()}
        if mixer:   # not drawn
            w["d_skip"] = jnp.ones((c.mamba_heads,), jnp.float32)
        return w

    final = drawn.gain(_key(key, "final_norm"), (H,), jnp.float32)
    return {
        "embed": drawn.matrix(_key(key, "embed"), (c.vocab_size, H), H,
                              c.dtype, 1.0 / c.embedding_multiplier),
        "final_norm": (final * (c.logits_scaling * c.embedding_multiplier)
                       ).astype(c.dtype),
        "layers": [layer(i, t == "mamba")
                   for i, t in enumerate(c.layer_types)]}


init_params = functools.partial(drawn.init, _draw_params)
param_specs = drawn.whole


# --- The block ---------------------------------------------------------------

def _moe(x, w: dict, c: SsmMoEConfig, counted):
    """The second half of every layer: x [.., H] -> (x', COUNTS)."""
    h = rms_norm(x, w["norm2"], c.rms_norm_eps)
    m, tally = expert_layer_counts(
        h, w, experts_per_token=c.experts_per_token,
        experts_held=c.experts_held, scoring=SOFTMAX_SELECTED,
        counted=counted)
    return x + m * jnp.asarray(c.residual_multiplier, x.dtype), tally


def _moe_in_pieces(x, w: dict, c: SsmMoEConfig, counted):
    """``_moe`` over a prompt's rows [S, H], ``MLP_ROWS`` at a time: the
    gathered (token, choice) pairs of a piece are ``MLP_ROWS x top-k`` rows
    and not ``S x top-k`` (at 8192 rows and top-10, 0.67 GB an array)."""
    S = x.shape[0]
    if S <= MLP_ROWS or S % MLP_ROWS:
        return _moe(x, w, c, counted)

    def piece(tally, xs):
        rows, real = xs
        rows, h = _moe(rows, w, c, real)
        return tally + h, rows

    tally, out = jax.lax.scan(
        piece, NO_COUNTS,
        (x.reshape(-1, MLP_ROWS, x.shape[1]), counted.reshape(-1, MLP_ROWS)))
    return out.reshape(S, -1), tally


def _projections(x, w: dict, c: SsmMoEConfig):
    """x [.., H] -> the gate z [.., I], the convolution's input [.., I + 2 N]
    and the step size's pre-activation [.., heads] in float32."""
    h = rms_norm(x, w["norm1"], c.rms_norm_eps)
    z, u = jnp.split(mm(h, w["w_in"]), (c.d_inner,), axis=-1)
    dt = jnp.dot(h, w["w_dt"], preferred_element_type=jnp.float32)
    return z, u, dt + w["b_dt"]


def _mixer_out(x, y, w: dict, c: SsmMoEConfig):
    """The gated y [.., I] through the norm over ALL its channels and the
    output projection, onto the residual."""
    g = rms_norm(y, w["mixer_norm"], c.rms_norm_eps)
    return x + mm(g, w["w_out"]) * jnp.asarray(c.residual_multiplier, x.dtype)


@jax.named_scope("mamba_mixer")
def _mixer_prefill(x, w: dict, c: SsmMoEConfig, length):
    """x [S, H] of one prompt -> (x', the convolution's tail [d_conv - 1,
    I + 2 N] and the scan state [N, I] after token ``length - 1``)."""
    S, K, I, N = x.shape[0], c.d_conv, c.d_inner, c.d_state
    z, u, dt = _projections(x, w, c)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))       # u_t = 0 for t < 0
    cc = sh.conv_tap([padded[j:j + S] for j in range(K)], w, x.dtype)
    # row t of ``padded`` is u at t - (K - 1): the K - 1 inputs before length
    tail = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)
    xs, b, cm = jnp.split(cc, (I, I + N), axis=-1)
    y, state = ssd.ssd_scan(
        xs, jax.nn.softplus(dt), b, cm, -jnp.exp(w["a_log"]), w["d_skip"],
        length, heads=c.mamba_heads, chunk=c.chunk)
    zf = z.astype(jnp.float32)
    y = (y.astype(jnp.float32) * zf * jax.nn.sigmoid(zf)).astype(x.dtype)
    return _mixer_out(x, y, w, c), tail, state


@jax.named_scope("mamba_mixer")
def _mixer_decode(x, w: dict, c: SsmMoEConfig, tail, ssm, i, walk: ss.Walk):
    """x [B, H], one token a slot; tail [d_conv - 1, B, I + 2 N]; ``ssm`` the
    held stack of scan states [mixers, B, N, I], of which this is mixer ``i``
    -> (x', tail', the stack). Where a slot is not among ``walk``'s active
    ones the tail is its own and its scan state is not touched."""
    I, N, P = c.d_inner, c.d_state, c.mamba_head_dim
    z, u, dt = _projections(x, w, c)
    taps = jnp.concatenate([tail, u[None].astype(tail.dtype)])
    cc = sh.conv_tap(taps, w, x.dtype)
    xs, b, cm = jnp.split(cc, (I, I + N), axis=-1)
    with jax.named_scope("state_update"):
        # a head's step size, decay and skip, once for each of its channels:
        # laid state-major this IS Mamba-1's step with a decay of one row
        y, ssm = ss.update_held(
            ssm, i, walk, xs, jnp.repeat(jax.nn.softplus(dt), P, axis=-1), z,
            b, cm, jnp.repeat(-jnp.exp(w["a_log"]), P)[None],
            jnp.repeat(w["d_skip"], P))
    return (_mixer_out(x, y, w, c),
            kv_kinds.keep(walk.active, taps[1:], tail, 1), ssm)


def _through_layers(params: Params, c: SsmMoEConfig, carry, mixer, attn):
    """The layers in their order, unrolled. ``mixer(carry, w, index) ->
    (carry, out)`` with ``index`` the mixer's place among the mixers (a Python
    int: a stack's layer is a static slice); ``attn`` alike. Returns (carry,
    the mixers' outs stacked, the attention layers')."""
    outs = {"mamba": [], "attention": []}
    for w, kind in zip(params["layers"], c.layer_types):
        step = mixer if kind == "mamba" else attn
        carry, out = step(carry, w, len(outs[kind]))
        outs[kind].append(out)
    return (carry, *(jax.tree.map(lambda *xs: jnp.stack(xs), *outs[kind])
                     for kind in ("mamba", "attention")))


def _head(params: Params, c: SsmMoEConfig, x):
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return jnp.einsum("...h,vh->...v", h, params["embed"],
                          preferred_element_type=jnp.float32
                          ) / c.logits_scaling


def _embed_scaled(params: Params, c: SsmMoEConfig, tokens):
    return embed(params, tokens, c.dtype) * jnp.asarray(
        c.embedding_multiplier, c.dtype)


def _counters(c: SsmMoEConfig, counted, tally) -> jnp.ndarray:
    """COUNTERS for one forward: every counted token makes
    ``experts_per_token`` choices in each layer."""
    pairs = jnp.sum(counted, dtype=jnp.int32) * c.num_layers
    return jnp.stack([pairs * c.experts_per_token, *tally, pairs])


def prefill(params: Params, cfg: SsmMoEConfig, tokens: jnp.ndarray, length):
    """tokens [1, S] (``length`` of them real) -> (float32 logits [V] of the
    last real position, the block ``kv_kinds.insert`` takes: ``k``, ``v``
    [attention layers, 1, S, KV, D], ``conv`` [mixers, d_conv - 1, 1, I + 2 N]
    and ``ssm`` [mixers, 1, N, I] at ``length``; COUNTERS)."""
    c = cfg
    counted = jnp.arange(tokens.shape[1]) < length
    rm = jnp.asarray(c.residual_multiplier, c.dtype)

    def mixer(carry, w, _i):
        x, tally = carry
        x, tail, state = _mixer_prefill(x[0], w, c, length)
        x, h = _moe_in_pieces(x, w, c, counted)
        return (x[None], tally + h), (tail, state)

    def attn(carry, w, _i):
        x, tally = carry
        q, k, v = sh.qkv(x, w, c)
        with jax.named_scope("full_attention"):
            a = blocked_attention(q, k, v, None, PREFILL_BLOCK,
                                  scale=c.attention_multiplier)
        x = x + mm(a.reshape(*x.shape[:2], c.q_dim), w["wo"]) * rm
        x, h = _moe_in_pieces(x[0], w, c, counted)
        return (x[None], tally + h), (k, v)

    (x, tally), (tails, states), (ks, vs) = _through_layers(
        params, c, (_embed_scaled(params, c, tokens), NO_COUNTS), mixer, attn)
    last = jax.lax.dynamic_index_in_dim(x[0], length - 1, keepdims=False)
    block = {"k": ks, "v": vs, "conv": tails[:, :, None],
             "ssm": states[:, None]}
    return _head(params, c, last), block, _counters(c, counted, tally)


def decode(params: Params, cfg: SsmMoEConfig, tokens: jnp.ndarray,
           cache: kv_kinds.LayeredKV, kinds, active: jnp.ndarray):
    """One token a slot against the VIEW of the held cache: tokens [B] at
    positions ``cache.lengths`` -> (float32 logits [B, V], what
    ``kv_kinds.append`` takes: this step's rows ``k``, ``v`` [attention
    layers, B, 1, KV, D] and the state stacks ``conv`` and ``ssm`` whole:
    each active slot's tail replaced, each active slot's scan state moved one
    step where it lies, an idle slot's untouched; COUNTERS over the
    ``active`` slots). A layer's tail is written back where it was read and
    the scan states are updated in the stack itself, so the step makes no
    second array of a stack's size."""
    c = cfg
    state_of = next(h for kd, h in zip(kinds, cache.held) if kd.state)
    rows_of = next(h for kd, h in zip(kinds, cache.held) if kd.rows)
    # a slot that is not active reads no row (its output is dropped)
    count = jnp.where(active, cache.lengths, 0)
    walk = ss.live_slots(active)    # once a step, not once a mixer
    rm = jnp.asarray(c.residual_multiplier, c.dtype)

    def mixer(carry, w, i):
        x, conv, ssm, tally = carry
        x, tail, ssm = _mixer_decode(x, w, c, conv[i], ssm, i, walk)
        conv = conv.at[i].set(tail)
        x, h = _moe(x, w, c, active)
        return (x, conv, ssm, tally + h), ()

    def attn(carry, w, i):
        x, conv, ssm, tally = carry
        q, k, v = sh.qkv(x[:, None], w, c)
        with jax.named_scope("full_attention"):
            a = decode_gqa_attention(q, k, v, rows_of["k"], rows_of["v"], i,
                                     count, scale=c.attention_multiplier)
        x = x + mm(a.reshape(-1, c.q_dim), w["wo"]) * rm
        x, h = _moe(x, w, c, active)
        return (x, conv, ssm, tally + h), (k, v)

    (x, conv, ssm, tally), _, (ks, vs) = _through_layers(
        params, c, (_embed_scaled(params, c, tokens), state_of["conv"],
                    state_of["ssm"], NO_COUNTS), mixer, attn)
    return (_head(params, c, x), {"k": ks, "v": vs, "conv": conv, "ssm": ssm},
            _counters(c, active, tally))
