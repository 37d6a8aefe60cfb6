"""State-space layers with an attention layer among them (the ``jamba`` block
of AI21's Jamba models, dense feed-forward), functional JAX.

What the other families have none of:

- **a Mamba-1 mixer** in most layers: a depthwise causal convolution of width
  ``d_conv``, a selective scan over time (``ops/selective_scan.py``) whose
  step size, input and output projections come from the token itself, three
  small RMSNorms on those (Jamba's), and a gate;
- **a slot's state has no row axis** there: the last ``d_conv - 1`` inputs of
  the convolution and the scan's state ``[d_state, d_inner]`` in float32,
  whatever the slot's length (``kv_kinds``: a kind with ``state``);
- **attention without any positional encoding**, ``num_heads`` query heads on
  ONE KV head, in layer ``i`` where ``i % attn_layer_period ==
  attn_layer_offset``: those layers hold a full stack of rows beside the state;
- embeddings tied to the head, unscaled.

    x = x + Mix_l(N1_l x);   x = x + MLP_l(N2_l x);   logits = N_f(x) E^T

Layout: the layers are stacks by kind, ``params["mamba"]`` (every leaf
``[mixers, ...]``, with the mixer's own MLP and norms) and ``params["attn"]``
(``[attention layers, ...]``). A period of the pattern is ``attn_layer_offset``
mixers, the attention layer, and the mixers after it; the forwards run ONE
``lax.scan`` over the periods whose body is a scan over each run of mixers
(a mixer's weights are read out of the stack by its index) and the attention
layer between them, so depth costs no compile time.

Two forwards for the serving engine (``models/families.py`` ``Layered``):
``prefill`` runs one prompt, right-padded to its bucket, and returns the
attention layers' K / V block and every mixer's state AT ``length`` (the
convolution's tail is read at ``length``, the scan stands still past it);
``decode`` runs one token a slot against the held cache and returns the new
rows and the state stacks with each active slot's state replaced in place
(the scan state by ``ops/selective_scan.py``'s ``update_held``: on a TPU a
kernel that walks the ACTIVE slots of the held stack, so an idle slot costs a
step nothing).

Not here, and refused at boot rather than served wrongly
(``models/families.py``): int8 weights or KV, paged KV, a prefix store (it
would have to snapshot state), a mesh of more than one chip, a checkpoint,
training (the scan has no backward pass).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kukeon_tpu.models import drawn, kv_kinds
from kukeon_tpu.models.expert_layer import swiglu
from kukeon_tpu.models.llama import embed, mm
from kukeon_tpu.ops import selective_scan as ss
from kukeon_tpu.ops.attention import blocked_attention, decode_gqa_attention
from kukeon_tpu.ops.norms import rms_norm

Params = dict[str, Any]
PREFILL_BLOCK = 512     # query rows a prefill attends at once (a bucket's, if fewer)
STATE_DTYPE = jnp.float32   # the scan state, held and updated (ssm_state_dtype)


@dataclasses.dataclass(frozen=True)
class SsmHybridConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192           # every layer's SwiGLU
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.num_layers % self.attn_layer_period
                or not 0 <= self.attn_layer_offset < self.attn_layer_period):
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of "
                f"{self.attn_layer_period} with the attention layer at "
                f"{self.attn_layer_offset}")

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.attn_layer_period

    @property
    def runs(self) -> tuple[int, int]:
        """Mixers of a period before and after its attention layer."""
        return (self.attn_layer_offset,
                self.attn_layer_period - self.attn_layer_offset - 1)

    @property
    def num_mixers(self) -> int:
        return self.num_periods * sum(self.runs)

    @property
    def layer_types(self) -> tuple[str, ...]:
        return tuple("attention" if i % self.attn_layer_period
                     == self.attn_layer_offset else "mamba"
                     for i in range(self.num_layers))

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def cache_kinds(self, max_seq_len: int) -> tuple[kv_kinds.CacheKind, ...]:
        """What a slot holds: every mixer's convolution tail (the taps before
        the slots, so that a tap is a ``[B, d_inner]`` tile) and scan state
        (state-major: ``ops/selective_scan.py`` says why), and every row of
        the attention layers."""
        M, I = self.num_mixers, self.d_inner
        types = self.layer_types
        return (
            kv_kinds.CacheKind(
                "state", tuple(i for i, t in enumerate(types) if t == "mamba"),
                state=(("conv", (M, self.d_conv - 1, None, I), self.dtype),
                       ("ssm", (M, None, self.d_state, I), STATE_DTYPE))),
            kv_kinds.CacheKind("full", tuple(range(self.num_periods)),
                               max_seq_len))


def jamba2_3b() -> SsmHybridConfig:
    """ai21labs/AI21-Jamba2-3B as published."""
    return SsmHybridConfig()


def ssm_hybrid_tiny() -> SsmHybridConfig:
    """Test size: two periods of (mixer, attention, mixer, mixer)."""
    return SsmHybridConfig(
        vocab_size=384, hidden_size=64, intermediate_size=128, num_layers=8,
        attn_layer_period=4, attn_layer_offset=1, num_heads=4, num_kv_heads=1,
        head_dim=16, d_state=8, d_conv=4, dt_rank=8, expand=2, max_seq_len=128,
        dtype=jnp.float32)


# --- Init --------------------------------------------------------------------
#
# The weights ARE their recipe (``models/drawn.py``): this family's table, and
# ``benchmark/reference/ssm_hybrid.py`` draws the same values without
# importing this file (tests/bench pins the two). The recipe leaves the state a
# long memory (a channel's decay a step runs from 0.999 to 0.2), or a check
# against the reference could not see a lost state: A = -(1 .. d_state) in
# every channel and D = 1 (S4D-real), the step size's bias the inverse
# softplus of a log-uniform draw in [1e-3, 1e-1], its projection small.

LEAVES = ("embed", "final_norm", "norm1", "norm2", "w_gate", "w_up", "w_down",
          "wq", "wk", "wv", "wo", "w_in", "conv_w", "conv_b", "w_x", "dt_norm",
          "b_norm", "c_norm", "w_dt", "b_dt", "w_out")
CONV_BIAS_STD = 0.1
DT_SCALE = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1

_key = functools.partial(drawn.leaf_key, LEAVES)


def dt_bias(key, shape):
    """softplus^-1 of a log-uniform step size, float32."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))


def _layer_leaves(c: SsmHybridConfig, mixer: bool) -> dict:
    """name -> (kind, shape, fan_in) of one layer's drawn leaves."""
    H, F, I = c.hidden_size, c.intermediate_size, c.d_inner
    R, N = c.dt_rank, c.d_state
    out = {"norm1": ("gain", (H,), 0), "norm2": ("gain", (H,), 0),
           "w_gate": ("matrix", (H, F), H), "w_up": ("matrix", (H, F), H),
           "w_down": ("matrix", (F, H), F)}
    if not mixer:
        out.update({"wq": ("matrix", (H, c.q_dim), H),
                    "wk": ("matrix", (H, c.kv_dim), H),
                    "wv": ("matrix", (H, c.kv_dim), H),
                    "wo": ("matrix", (c.q_dim, H), c.q_dim)})
        return out
    out.update({
        "w_in": ("matrix", (H, 2 * I), H),
        # drawn [I, d_conv] as published and held taps-major, as the tail is
        "conv_w": ("taps", (I, c.d_conv), c.d_conv),
        "conv_b": ("conv_bias", (I,), 0),
        "w_x": ("matrix", (I, R + 2 * N), I),
        "dt_norm": ("gain", (R,), 0), "b_norm": ("gain", (N,), 0),
        "c_norm": ("gain", (N,), 0),
        "w_dt": ("dt", (R, I), R), "b_dt": ("dt_bias", (I,), 0),
        "w_out": ("matrix", (I, H), I)})
    return out


def _draw(key, c: SsmHybridConfig, name, kind, shape, fan_in, layer):
    k = _key(key, name, layer)
    if kind == "taps":
        return drawn.matrix(k, shape, fan_in, c.dtype).T
    if kind == "conv_bias":
        return (CONV_BIAS_STD * jax.random.normal(k, shape, jnp.float32)
                ).astype(c.dtype)
    if kind == "dt":
        return drawn.matrix(k, shape, fan_in, c.dtype, DT_SCALE)
    if kind == "dt_bias":
        return dt_bias(k, shape)
    return drawn.draw(LEAVES, key, c, name, kind, shape, fan_in, layer)


def _draw_params(key: jax.Array, c: SsmHybridConfig) -> Params:
    types = c.layer_types
    H, N, I = c.hidden_size, c.d_state, c.d_inner

    def stack(mixer: bool):
        numbers = jnp.asarray([i for i, t in enumerate(types)
                               if (t == "mamba") == mixer], jnp.int32)
        return {name: jax.lax.map(
            lambda layer, n=name, s=spec: _draw(key, c, n, *s, layer), numbers)
            for name, spec in _layer_leaves(c, mixer).items()}

    mamba = stack(True)
    M = c.num_mixers
    # not drawn: S4D-real, the same in every channel and layer
    mamba["a_log"] = jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
        (M, N, I))
    mamba["d_skip"] = jnp.ones((M, I), jnp.float32)
    return {
        "embed": drawn.matrix(_key(key, "embed"), (c.vocab_size, H), H,
                              c.dtype),
        "final_norm": drawn.gain(_key(key, "final_norm"), (H,), c.dtype),
        "mamba": mamba, "attn": stack(False)}


init_params = functools.partial(drawn.init, _draw_params)
param_specs = drawn.whole


# --- The block ---------------------------------------------------------------

def _at(stack: dict, i) -> dict:
    """Layer ``i`` (traced) of a stack of leaves."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _mlp(x, w: dict, c: SsmHybridConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, w["norm2"], c.rms_norm_eps)
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def _scan_inputs(cc, w: dict, c: SsmHybridConfig):
    """The convolved input [.., I] -> (step size d [.., I] float32, B, C
    [.., N] float32): the three projections of ``w_x`` in float32, each
    through its RMSNorm, the step size through its own projection, bias and
    softplus."""
    f32 = jnp.float32
    R, N = c.dt_rank, c.d_state
    rbc = jnp.dot(cc, w["w_x"], preferred_element_type=f32)
    r, b, cm = jnp.split(rbc, (R, R + N), axis=-1)
    r = rms_norm(r, w["dt_norm"], c.rms_norm_eps)
    b = rms_norm(b, w["b_norm"], c.rms_norm_eps)
    cm = rms_norm(cm, w["c_norm"], c.rms_norm_eps)
    d = jax.nn.softplus(
        jnp.dot(r.astype(cc.dtype), w["w_dt"], preferred_element_type=f32)
        + w["b_dt"])
    return d, b, cm


def conv_tap(taps, w: dict, dtype):
    """The causal convolution at its newest tap: ``taps`` the d_conv inputs
    [d_conv, .., I], oldest first."""
    f32 = jnp.float32
    cv = w["conv_b"].astype(f32) + sum(
        w["conv_w"][j].astype(f32) * taps[j].astype(f32)
        for j in range(len(taps)))
    return jax.nn.silu(cv).astype(dtype)


@jax.named_scope("mamba_mixer")
def _mixer_prefill(x, w: dict, c: SsmHybridConfig, length):
    """x [S, H] of one prompt -> (x', the convolution's tail [d_conv - 1, I]
    and the scan state [N, I] after token ``length - 1``)."""
    S, K = x.shape[0], c.d_conv
    h = rms_norm(x, w["norm1"], c.rms_norm_eps)
    u, z = jnp.split(mm(h, w["w_in"]), 2, axis=-1)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))       # u_t = 0 for t < 0
    cc = conv_tap([padded[j:j + S] for j in range(K)], w, x.dtype)
    # row t of ``padded`` is u at t - (K - 1): the K - 1 inputs before length
    tail = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)
    d, b, cm = _scan_inputs(cc, w, c)
    y, state = ss.selective_scan(cc, d, z, b, cm, -jnp.exp(w["a_log"]),
                                 w["d_skip"], length)
    return x + mm(y, w["w_out"]), tail, state


@jax.named_scope("mamba_mixer")
def _mixer_decode(x, w: dict, c: SsmHybridConfig, tail, ssm, i,
                  walk: ss.Walk):
    """x [B, H], one token a slot; tail [d_conv - 1, B, I]; ``ssm`` the held
    stack of scan states [mixers, B, N, I], of which this is mixer ``i``
    -> (x', tail', the stack). Where a slot is not among ``walk``'s active
    ones the tail is its own and its scan state is not touched
    (``ss.update_held``)."""
    h = rms_norm(x, w["norm1"], c.rms_norm_eps)
    u, z = jnp.split(mm(h, w["w_in"]), 2, axis=-1)
    taps = jnp.concatenate([tail, u[None].astype(tail.dtype)])
    cc = conv_tap(taps, w, x.dtype)
    d, b, cm = _scan_inputs(cc, w, c)
    y, ssm = ss.update_held(ssm, i, walk, cc, d, z, b, cm,
                            -jnp.exp(w["a_log"]), w["d_skip"])
    return (x + mm(y, w["w_out"]),
            kv_kinds.keep(walk.active, taps[1:], tail, 1), ssm)


def qkv(x, w: dict, c: SsmHybridConfig):
    """x [B, S, H] -> q [B, S, NH, D], k, v [B, S, KV, D]; no rotary."""
    B, S = x.shape[:2]
    h = rms_norm(x, w["norm1"], c.rms_norm_eps)
    return (mm(h, w["wq"]).reshape(B, S, c.num_heads, c.head_dim),
            mm(h, w["wk"]).reshape(B, S, c.num_kv_heads, c.head_dim),
            mm(h, w["wv"]).reshape(B, S, c.num_kv_heads, c.head_dim))


def _through_layers(params: Params, c: SsmHybridConfig, carry, mixer, attn):
    """ONE scan over the periods: a scan over the mixers before the attention
    layer, the attention layer, a scan over the mixers after it.
    ``mixer(carry, w, index) -> (carry, out)`` with ``index`` the mixer's place
    in its stack; ``attn(carry, w, index) -> (carry, out)``. Returns (carry,
    the mixers' outs stacked in the stack's order, the attention layers')."""
    before, after = c.runs
    per = before + after

    def run(carry, first, count):
        return jax.lax.scan(
            lambda carry, i: mixer(carry, _at(params["mamba"], i), i),
            carry, first + jnp.arange(count))

    def period(carry, p):
        carry, head = run(carry, p * per, before)
        carry, mid = attn(carry, _at(params["attn"], p), p)
        carry, tail = run(carry, p * per + before, after)
        return carry, (jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), head, tail), mid)

    carry, (mixers, attns) = jax.lax.scan(period, carry,
                                          jnp.arange(c.num_periods))
    # [periods, mixers a period, ...] -> the stack's order
    return carry, jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), mixers), attns


def _head(params: Params, c: SsmHybridConfig, x):
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return jnp.einsum("...h,vh->...v", h, params["embed"],
                          preferred_element_type=jnp.float32)


_NO_COUNTERS = np.zeros((0,), np.int32)


def prefill(params: Params, cfg: SsmHybridConfig, tokens: jnp.ndarray, length):
    """tokens [1, S] (``length`` of them real) -> (float32 logits [V] of the
    last real position, the block ``kv_kinds.insert`` takes: ``k``, ``v``
    [attention layers, 1, S, KV, D], ``conv`` [mixers, d_conv - 1, 1, I] and
    ``ssm`` [mixers, 1, N, I] at ``length``; no counters)."""
    c = cfg

    def mixer(x, w, _i):
        x, tail, state = _mixer_prefill(x[0], w, c, length)
        return _mlp(x, w, c)[None], (tail, state)

    def attn(x, w, _i):
        q, k, v = qkv(x, w, c)
        with jax.named_scope("full_attention"):
            a = blocked_attention(q, k, v, None, PREFILL_BLOCK)
        x = x + mm(a.reshape(*x.shape[:2], c.q_dim), w["wo"])
        return _mlp(x, w, c), (k, v)

    x, (tails, states), (ks, vs) = _through_layers(
        params, c, embed(params, tokens, c.dtype), mixer, attn)
    last = jax.lax.dynamic_index_in_dim(x[0], length - 1, keepdims=False)
    block = {"k": ks, "v": vs, "conv": tails[:, :, None],
             "ssm": states[:, None]}
    return _head(params, c, last), block, _NO_COUNTERS


def decode(params: Params, cfg: SsmHybridConfig, tokens: jnp.ndarray,
           cache: kv_kinds.LayeredKV, kinds, active: jnp.ndarray):
    """One token a slot against the VIEW of the held cache: tokens [B] at
    positions ``cache.lengths`` -> (float32 logits [B, V], what
    ``kv_kinds.append`` takes: this step's rows ``k``, ``v`` [attention
    layers, B, 1, KV, D] and the state stacks ``conv`` and ``ssm`` whole:
    each active slot's tail replaced, each active slot's scan state moved one
    step where it lies, an idle slot's untouched; no counters). The stacks
    ride in the scans' carry, a layer's tail is written back where it was
    read and the scan states are updated in the stack itself, so the step
    makes no second array of a stack's size."""
    c = cfg
    state_of = next(h for kd, h in zip(kinds, cache.held) if kd.state)
    rows_of = next(h for kd, h in zip(kinds, cache.held) if kd.rows)
    # a slot that is not active reads no row (its output is dropped)
    count = jnp.where(active, cache.lengths, 0)
    walk = ss.live_slots(active)    # once a step, not once a mixer

    def mixer(carry, w, i):
        x, conv, ssm = carry
        x, tail, ssm = _mixer_decode(
            x, w, c, jax.lax.dynamic_index_in_dim(conv, i, keepdims=False),
            ssm, i, walk)
        conv = jax.lax.dynamic_update_index_in_dim(conv, tail, i, 0)
        return (_mlp(x, w, c), conv, ssm), ()

    def attn(carry, w, i):
        x, conv, ssm = carry
        q, k, v = qkv(x[:, None], w, c)
        with jax.named_scope("full_attention"):
            a = decode_gqa_attention(q, k, v, rows_of["k"], rows_of["v"], i,
                                     count)
        x = x + mm(a.reshape(-1, c.q_dim), w["wo"])
        return (_mlp(x, w, c), conv, ssm), (k, v)

    (x, conv, ssm), _, (ks, vs) = _through_layers(
        params, c, (embed(params, tokens, c.dtype), state_of["conv"],
                    state_of["ssm"]), mixer, attn)
    return (_head(params, c, x), {"k": ks, "v": vs, "conv": conv, "ssm": ssm},
            _NO_COUNTERS)
