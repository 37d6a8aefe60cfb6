"""Latent attention that selects its rows, over an expert layer with
group-limited routing (the ``deepseek_v32`` block: DeepSeek-V3.2-Exp),
functional JAX.

What the other families have none of:

- **a latent cache**: a layer caches ONE row a token and no K or V a head:
  ``[c | kr]``, the normed ``kv_lora_rank`` latent and the rotated
  ``qk_rope_head_dim`` part every head shares, and beside it the indexer's key
  ``kI`` (``models/kv_kinds.py``: rows of named arrays, no head axis);
- **an indexer that selects**: ``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))``
  over ``index_n_heads`` small heads, and the attention of token ``t`` sees the
  ``index_topk`` positions of largest ``I`` only (``ops/sparse_attention.py``);
- **two forms of one attention**: ``prefill`` expands the latent into each
  head's ``k_nope`` and ``v`` (``Wkv_b``) and runs masked flash attention, the
  selection as a mask over causal scores; ``decode`` absorbs ``Wkv_b`` into the
  query and the output (``q_nope Wkv_b^K`` against ``c``, the mix of latents
  through ``Wkv_b^V``) and attends the slot's live rows IN PLACE under the
  selection's threshold, one shared latent head for all ``num_heads``. Both
  are the equations of ``benchmark/reference/sparse_latent_moe.py``; neither
  drops a term;
- **YaRN** frequencies for both rotations and its factor on the softmax scale
  (``ops/rope.py``); the attention's rotated pairs are neighbours
  (interleaved), the indexer's are split halves, as the published code has it;
- **group-limited routing** (``models/expert_layer.py``: the experts in
  ``n_group`` runs, ``topk_group`` kept), a selection bias whose load is the
  same for every seed (``_bias``), plain pre-norm residuals, an unscaled
  embedding.

A layer's attention is DATA (``LatentAttention``: heads, ranks, widths, rotary
base, window), and a config may state two kinds of layer (``layer_types``),
each with a latent attention of its own (the ``dots3_note`` block:
dots3-note-prev):

- a ``full_attention`` layer is the selecting block above;
- a ``sliding_attention`` layer has NO indexer and sees the ``window``
  positions up to its own: its cache is a RING of its own latent row, a
  prefill attends a band of expanded keys, and a decode step streams the
  ring's live rows in place through the same absorbed form
  (``sa.window_decode_attention``);
- both may carry a gate a head (``head_gate``: head ``i``'s output times
  ``sigmoid(h Wg)_i`` before ``Wo``) and a rescale of the normed latents
  (``lora_rescale``: ``sqrt(hidden / rank)`` on ``cq`` and on ``c``; the
  cached row holds the scaled ``c``).

``deepseek_v32_exp()`` is the case "every layer full, no gate, no rescale,
YaRN, 8 groups" of the same functions.

Layout: ``params["layers"]`` is a list, the leading dense layers and then the
expert layers, each with its own leaves, and a forward walks it unrolled: a
held stack of experts sliced out of a scan's stacked weights is COPIED on its
way into the ragged product (a custom call), 1.4 GB a layer of every decode
step at the published widths, where a layer's own leaf is read in place. A
prefill works a layer through in pieces so that a 32768-row prompt never holds
more than a piece's temporaries: attention by chunks of ``ATTN_CHUNK`` queries
(the mask of a chunk is ``[chunk, S]`` int8) and, inside a chunk, by groups of
``HEAD_GROUP`` heads (a group's expanded K and V are recomputed a chunk, 4% of
the attention's operations at 32768 rows); the MLP by runs of ``MLP_ROWS`` rows (the expert
layer sorts every (token, choice) pair of its rows).

Not here, and refused at boot rather than served wrongly
(``models/families.py``): int8 weights or KV, paged KV, a prefix store, a mesh
of more than one chip, the multi-token-prediction module, training.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from kukeon_tpu.models import drawn, kv_kinds
from kukeon_tpu.models.expert_layer import (
    COUNTS, NO_COUNTS, expert_layer_counts, select, swiglu)
from kukeon_tpu.models.llama import embed, mm
from kukeon_tpu.ops import rope
from kukeon_tpu.ops import sparse_attention as sa
from kukeon_tpu.ops.norms import rms_norm

Params = dict[str, Any]
# Device-summed counters a forward returns beside its logits, in this order:
# the routers' choices and the expert layers' COUNTS (as window_moe's), the
# token x expert-layer pairs those choices were made for, and of the decode
# steps the latent rows attended and the rows that were live for them.
COUNTERS = ("kukeon_moe_routed_total", *COUNTS,
            "kukeon_moe_routed_tokens_total",
            "kukeon_sparse_rows_selected_total",
            "kukeon_sparse_rows_live_total")
# ... and, where a config has window layers, of the decode steps the ring rows
# (and the step's own) those layers attended for the active slots, and the
# tokens those slots held (what a full stack would have had them attend).
WINDOW_COUNTERS = ("kukeon_window_latent_rows_read_total",
                   "kukeon_window_latent_rows_held_total")
SLIDING, FULL = "sliding_attention", "full_attention"
ATTN_CHUNK = 4096       # queries a prefill attends at once (a bucket's, if fewer)
WINDOW_CHUNK = 1024     # ... of a window layer, whose band of keys is short
RING_TILE = 16          # a ring holds whole tiles of rows (bf16: 16 a tile)
HEAD_GROUP = 16         # heads whose K and V a prefill expands at once
MLP_ROWS = 2048         # rows a prefill takes through an MLP at once
LN_EPS = 1e-6           # the indexer's LayerNorm


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The latent attention of one kind of layer."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    # The positions a token sees, itself among them; 0: those its indexer
    # selects among all before it.
    window: int = 0
    # (factor, original_max, beta_fast, beta_slow, mscale) where the served
    # context passes the trained one; None: plain frequencies.
    yarn: tuple | None = None

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def head_group(self) -> int:
        return min(HEAD_GROUP, self.num_heads)

    @property
    def latent_width(self) -> int:
        """The cached row [c | kr | 0 ...]: whole lanes. The chip's tiling
        holds 576 values a row as 640 whatever the program says; said here,
        the row is what the decode step's copies read and nothing relays it
        out."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim)
                 // sa.LANES) * sa.LANES

    @property
    def softmax_scale(self) -> float:
        m = rope.yarn_mscale(self.yarn[0], self.yarn[4]) if self.yarn else 1.0
        return self.head_dim ** -0.5 * m * m

    def inv_freq(self) -> jnp.ndarray:
        if not self.yarn:
            return rope.rope_frequencies(self.qk_rope_head_dim,
                                         self.rope_theta)
        return rope.yarn_frequencies(self.qk_rope_head_dim, self.rope_theta,
                                     *self.yarn[:4])


@dataclasses.dataclass(frozen=True)
class SparseLatentMoEConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # the dense layers' SwiGLU
    moe_intermediate_size: int = 2048       # one expert's, and the shared one's
    num_layers: int = 61
    num_dense_layers: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    num_experts: int = 256                  # the router's width
    experts_per_token: int = 8
    experts_held: tuple[int, int] = (0, 256)    # (first, count) on this chip
    n_group: int = 8
    topk_group: int = 4
    route_scale: float = 2.5
    route_norm: bool = True
    rope_theta: float = 10_000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 163840
    dtype: Any = jnp.bfloat16
    # The fields above that state an attention are the FULL layers'. Every
    # layer is one unless ``layer_types`` says otherwise; a
    # ``sliding_attention`` layer runs ``sliding``, a latent attention of its
    # own with a window and no indexer.
    layer_types: tuple[str, ...] = ()
    sliding: LatentAttention | None = None
    head_gate: bool = False         # sigmoid(h Wg), a value a head, on both
    lora_rescale: bool = False      # sqrt(hidden / rank) on the normed latents

    def __post_init__(self):
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if (self.num_experts % self.n_group
                or self.num_heads % self.full.head_group
                or not 0 < self.num_dense_layers < self.num_layers):
            raise ValueError(
                f"{self.num_experts} experts in {self.n_group} groups, "
                f"{self.num_heads} heads, {self.num_dense_layers} dense of "
                f"{self.num_layers} layers")
        types = self.layer_types
        if types and (len(types) != self.num_layers
                      or set(types) - {SLIDING, FULL}):
            raise ValueError(f"layer_types {types} for {self.num_layers} "
                             "layers")
        if SLIDING in types and not (
                self.sliding and self.sliding.window > 1
                and self.sliding.num_heads % self.sliding.head_group == 0):
            raise ValueError("sliding_attention layers need ``sliding``, a "
                             f"latent attention with a window; got "
                             f"{self.sliding}")

    # What the engine asks every config for; this family's cache has neither.
    @property
    def num_kv_heads(self) -> int:
        return self.num_heads

    @property
    def head_dim(self) -> int:
        return self.full.head_dim

    @property
    def yarn(self) -> bool:
        """The published code stretches the rotation whenever the served
        context passes the trained one (a factor of 1 stretches nothing)."""
        return (self.rope_factor != 1.0
                and self.max_seq_len > self.rope_original_max)

    @property
    def full(self) -> LatentAttention:
        """The selecting layers' attention, from the fields above."""
        return LatentAttention(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, yarn=(
                self.rope_factor, self.rope_original_max, self.rope_beta_fast,
                self.rope_beta_slow, self.rope_mscale) if self.yarn else None)

    @property
    def softmax_scale(self) -> float:
        """The full layers'."""
        return self.full.softmax_scale

    def attention(self, layer: int) -> LatentAttention:
        sliding = self.layer_types and self.layer_types[layer] == SLIDING
        return self.sliding if sliding else self.full

    def layers_of(self, layer_type: str) -> tuple[int, ...]:
        types = self.layer_types or (FULL,) * self.num_layers
        return tuple(i for i, t in enumerate(types) if t == layer_type)

    def cache_kinds(self, max_seq_len: int) -> tuple[kv_kinds.CacheKind, ...]:
        """A full layer holds, for every position, the latent row and the
        indexer's key; a decode step scores the keys of the live rows and
        attends the ``index_topk`` best latent rows among them. A window
        layer holds a ring of its own latent row: the ``window - 1``
        positions behind a step (its own row takes part unwritten), in whole
        tiles of rows."""
        kinds = []
        if self.layers_of(FULL):
            kinds.append(kv_kinds.CacheKind(
                "latent", self.layers_of(FULL), max_seq_len,
                arrays=(("kidx", self.index_head_dim),
                        ("ckv", self.full.latent_width)),
                select=self.index_topk))
        if self.layers_of(SLIDING):
            behind = min(self.sliding.window - 1, max_seq_len)
            kinds.append(kv_kinds.CacheKind(
                "window_latent", self.layers_of(SLIDING),
                -(-behind // RING_TILE) * RING_TILE, ring=True,
                arrays=(("wckv", self.sliding.latent_width),),
                window=self.sliding.window))
        return tuple(kinds)


def counters(cfg: SparseLatentMoEConfig) -> tuple[str, ...]:
    """The names of what this config's forwards sum, in their order."""
    return COUNTERS + (WINDOW_COUNTERS if cfg.layers_of(SLIDING) else ())


def deepseek_v32_exp() -> SparseLatentMoEConfig:
    """deepseek-ai/DeepSeek-V3.2-Exp as published (671B-A37B)."""
    return SparseLatentMoEConfig()


def dots3_note_prev() -> SparseLatentMoEConfig:
    """dots-studio/dots3-note-prev as published (288B-A17B), the language
    model: a dense full layer, then periods of one full layer and three
    window layers; one group of experts, no YaRN."""
    return SparseLatentMoEConfig(
        vocab_size=152064, hidden_size=5120, intermediate_size=13824,
        moe_intermediate_size=1536, num_layers=46, num_dense_layers=1,
        num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_group=1, topk_group=1, route_scale=1.0, rope_theta=8e7,
        rope_factor=1.0, rms_norm_eps=1e-5, max_seq_len=524288,
        layer_types=(FULL,) + (FULL, SLIDING, SLIDING, SLIDING) * 11 + (FULL,),
        sliding=LatentAttention(64, 1024, 1024, 192, 64, 128, 5e4, window=513),
        head_gate=True, lora_rescale=True)


def mixed_latent_moe_tiny() -> SparseLatentMoEConfig:
    """Test size of the two kinds side by side: a dense full layer, a full
    expert layer that selects 8 rows, two window layers that see 7 positions
    (their ring holds 16 rows, more than the 6 behind a step) with other head
    counts and widths, a gate a head, the rescale, one group of experts."""
    return dataclasses.replace(
        sparse_latent_moe_tiny(), num_layers=4, n_group=1, topk_group=1,
        route_scale=1.0, rope_factor=1.0, rms_norm_eps=1e-5,
        layer_types=(FULL, FULL, SLIDING, SLIDING),
        sliding=LatentAttention(2, 40, 56, 24, 8, 16, 5e4, window=7),
        head_gate=True, lora_rescale=True)


def sparse_latent_moe_tiny() -> SparseLatentMoEConfig:
    """Test size: one dense layer and two expert layers, a selection of 8 rows
    (every test prompt is longer), this chip holding 4 of 16 experts in 4
    groups of which 2 are kept."""
    return SparseLatentMoEConfig(
        vocab_size=384, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_layers=3, num_dense_layers=1,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=16, index_head_dim=16,
        index_topk=8, num_experts=16, experts_per_token=4,
        experts_held=(4, 4), n_group=4, topk_group=2, rope_original_max=16,
        max_seq_len=64, dtype=jnp.float32)


# --- Init --------------------------------------------------------------------
#
# The weights ARE their recipe (``models/drawn.py``): this family's table, and
# ``benchmark/reference/sparse_latent_moe.py`` draws the same values without
# importing this file (tests/bench pins the two).

# ``wkv_b`` is drawn as published, [R, heads x (k_nope | v)], and HELD as its
# two halves a head, ``wkv_bk`` [NH, Dn, R] and ``wkv_bv`` [NH, R, Dv]: the
# operands of the absorbed decode's two batched products, read in place.
LEAVES = ("embed", "lm_head", "final_norm", "norm1", "norm2", "wq_a",
          "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wi_q", "wi_k",
          "wi_k_gain", "wi_k_shift", "wi_w", "w_gate", "w_up", "w_down",
          "router", "bias", "s_gate", "s_up", "s_down", "e_gate", "e_up",
          "e_down")
# Leaves only a config with ``head_gate`` has; their keys follow LEAVES'.
GATE_LEAVES = ("wg",)
SHIFT_STD = 0.1
BIAS_SAMPLES = 1 << 16
BIAS_STEPS = 32
BIAS_STEP = 0.02

_key = functools.partial(drawn.leaf_key, LEAVES + GATE_LEAVES)


def _bias(key, router, gain, c: SparseLatentMoEConfig) -> jnp.ndarray:
    """``e_score_correction_bias``: what the published training moves until
    the experts' loads are even, FITTED to this seed's router as training
    fits it, so that a seed cannot change how much work a chip that holds a
    block of experts is given (left at zero a Gaussian router's own draw
    moves a block of 16's load by 3.5% between seeds). The logits of a normed
    token whose direction is isotropic are N(0, A^T A) with A = the norm's
    gain x the router; over ``BIAS_SAMPLES`` such draws the bias takes
    ``BIAS_STEPS`` steps of the training's rule (down where an expert's load
    is over even, up where under), each the size of its relative excess."""
    a = gain.astype(jnp.float32)[:, None] * router
    cov = jnp.dot(a.T, a, precision=jax.lax.Precision.HIGHEST)
    logits = jnp.dot(
        jax.random.normal(key, (BIAS_SAMPLES, c.num_experts), jnp.float32),
        jnp.linalg.cholesky(cov).T, precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    even = BIAS_SAMPLES * c.experts_per_token / c.num_experts

    def step(_, b):
        sel = select(s + b, c.experts_per_token, c.n_group, c.topk_group)
        load = jnp.zeros((c.num_experts,), jnp.float32).at[
            sel.reshape(-1)].add(1.0)
        return b - BIAS_STEP * (load / even - 1.0)

    return jax.lax.fori_loop(0, BIAS_STEPS, step,
                             jnp.zeros((c.num_experts,), jnp.float32))


def _layer_leaves(c: SparseLatentMoEConfig, dense: bool,
                  a: LatentAttention | None = None) -> dict:
    """name -> (kind, shape, fan_in) of one layer's leaves; ``a`` its
    attention (None: a full layer's)."""
    a = a or c.full
    H, Im, Q, R = (c.hidden_size, c.moe_intermediate_size, a.q_lora_rank,
                   a.kv_lora_rank)
    NH, Dk, Dv = a.num_heads, a.head_dim, a.v_head_dim
    Hi, Di = c.index_n_heads, c.index_head_dim
    # What reads a RESCALED latent is drawn at the model's width: the rescale
    # is there for matrices drawn so (sqrt(H / rank) x a latent of unit
    # values x H ** -0.5 gives unit queries and keys). Drawn at the rank's
    # fan-in they would give attention logits of deviation 7 (full) and 5
    # (window), a softmax that is ONE position, and bf16 rounding would
    # choose which.
    Fq, Fr = (H, H) if c.lora_rescale else (Q, R)
    out = {
        "norm1": ("gain", (H,), 0), "norm2": ("gain", (H,), 0),
        "wq_a": ("matrix", (H, Q), H), "q_norm": ("gain", (Q,), 0),
        "wq_b": ("matrix", (Q, NH * Dk), Fq),
        "wkv_a": ("matrix", (H, R + a.qk_rope_head_dim), H),
        "kv_norm": ("gain", (R,), 0),
        # a head's columns of wkv_b: its k_nope, then its v
        "wkv_bk": ("up_k", (R, NH * (a.qk_nope_head_dim + Dv)), Fr),
        "wkv_bv": ("up_v", (R, NH * (a.qk_nope_head_dim + Dv)), Fr),
        "wo": ("matrix", (NH * Dv, H), NH * Dv)}
    if not a.window:        # the indexer
        out.update({
            "wi_q": ("matrix", (Q, Hi * Di), Fq),
            "wi_k": ("matrix", (H, Di), H),
            "wi_k_gain": ("gain", (Di,), 0),
            "wi_k_shift": ("shift", (Di,), 0),
            "wi_w": ("matrix", (H, Hi), H)})
    if c.head_gate:
        out["wg"] = ("matrix", (H, NH), H)
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


def _draw(key, c, name, kind, shape, fan_in, layer):
    if kind in ("up_k", "up_v"):
        a = c.attention(layer)
        both = drawn.matrix(_key(key, "wkv_b", layer), shape, fan_in, c.dtype
                            ).reshape(shape[0], a.num_heads, -1)
        n = a.qk_nope_head_dim
        return (jnp.transpose(both[..., :n], (1, 2, 0)) if kind == "up_k"
                else jnp.transpose(both[..., n:], (1, 0, 2)))
    if kind == "shift":
        return (SHIFT_STD * jax.random.normal(_key(key, name, layer), shape,
                                              jnp.float32)).astype(c.dtype)
    if kind == "bias":      # fitted to the layer's own router and norm
        spec = _layer_leaves(c, False)
        return _bias(_key(key, name, layer),
                     _draw(key, c, "router", *spec["router"], layer),
                     _draw(key, c, "norm2", *spec["norm2"], layer), c)
    return drawn.draw(LEAVES + GATE_LEAVES, key, c, name, kind, shape, fan_in,
                      layer)


def _draw_params(key: jax.Array, c: SparseLatentMoEConfig) -> Params:
    H, V, Ld = c.hidden_size, c.vocab_size, c.num_dense_layers
    return {
        # rows of unit variance, as a trained stream is its token's own vector
        # before anything else: at fan_in ** -0.5 the first attention's mean
        # over the selected rows, which a sequence's tokens share, outweighs
        # the token (0.022 against 0.012 a value at the published widths),
        # every router then sees one direction a sequence, and a seed's
        # prompts decide which experts work
        "embed": drawn.matrix(_key(key, "embed"), (V, H), 1, c.dtype),
        "lm_head": drawn.matrix(_key(key, "lm_head"), (H, V), H, c.dtype),
        "final_norm": drawn.gain(_key(key, "final_norm"), (H,), c.dtype),
        "layers": [
            {name: _draw(key, c, name, *spec, i)
             for name, spec in _layer_leaves(
                 c, i < Ld, c.attention(i)).items()}
            for i in range(c.num_layers)],
    }


init_params = functools.partial(drawn.init, _draw_params)
param_specs = drawn.whole


# --- The block ---------------------------------------------------------------

def _rotate_first(x, positions, a: LatentAttention, interleaved: bool):
    """x [..., S, heads, D] with its first ``qk_rope_head_dim`` rotated."""
    r = a.qk_rope_head_dim
    return jnp.concatenate(
        [rope.rotate(x[..., :r], positions, a.inv_freq(), interleaved),
         x[..., r:]], axis=-1)


def _rescaled(x, c: SparseLatentMoEConfig, rank: int):
    """A normed latent times ``sqrt(hidden / rank)`` where the config says
    so."""
    if not c.lora_rescale:
        return x
    return x * jnp.asarray((c.hidden_size / rank) ** 0.5, x.dtype)


def _latents(h, w: dict, c: SparseLatentMoEConfig, a: LatentAttention,
             positions):
    """The normed input h [.., S, H] at ``positions`` [.., S] -> (cq [.., S,
    Q], the latent row [c | kr] [.., S, R + Dr] that is cached)."""
    R = a.kv_lora_rank
    # A product ENDS at its flat result wherever a reshape to heads or a
    # rotation follows (``llama._qkv`` says why: fused into the product they
    # make the compiler transpose and copy the weight first).
    cq, kv = jax.lax.optimization_barrier(
        (mm(h, w["wq_a"]), mm(h, w["wkv_a"])))
    cq = _rescaled(rms_norm(cq, w["q_norm"], c.rms_norm_eps), c,
                   a.q_lora_rank)
    kr = rope.rotate(kv[..., None, R:], positions, a.inv_freq(),
                     interleaved=True)[..., 0, :]
    pad = jnp.zeros((*kv.shape[:-1], a.latent_width - kv.shape[-1]), kv.dtype)
    return cq, jnp.concatenate(
        [_rescaled(rms_norm(kv[..., :R], w["kv_norm"], c.rms_norm_eps), c, R),
         kr, pad], axis=-1)


@jax.named_scope("indexer")
def _index_key(h, w: dict, c: SparseLatentMoEConfig, positions):
    """The normed input -> kI [.., S, Di], the cached index row: a LayerNorm
    of its projection, the first ``qk_rope_head_dim`` rotated in split
    halves."""
    k = mm(h, w["wi_k"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + LN_EPS)
    k = (k * w["wi_k_gain"].astype(jnp.float32)
         + w["wi_k_shift"].astype(jnp.float32)).astype(h.dtype)
    return _rotate_first(k[..., None, :], positions, c.full, False)[..., 0, :]


@jax.named_scope("indexer")
def _index_query(cq, wts, w: dict, c: SparseLatentMoEConfig, positions):
    """The query latent and ``weights_proj`` of the normed input (``wts``
    [.., S, Hi]) -> (qI [.., S, Hi, Di], rotated as the key is, and the
    heads' weights [.., S, Hi] float32)."""
    Hi, Di = c.index_n_heads, c.index_head_dim
    qi = jax.lax.optimization_barrier(mm(cq, w["wi_q"]))
    qi = qi.reshape(*cq.shape[:-1], Hi, Di)
    wts = wts.astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return _rotate_first(qi, positions, c.full, False), wts


def _queries(cq, wq_b, a: LatentAttention, positions, heads: int):
    """cq [.., S, Q] through ``heads`` heads' columns of Wq_b -> [.., S,
    heads, Dk], the rotated part (the last ``qk_rope_head_dim``, neighbours
    paired) rotated."""
    q = jax.lax.optimization_barrier(mm(cq, wq_b)).reshape(
        *cq.shape[:-1], heads, a.head_dim)
    n = a.qk_nope_head_dim
    return jnp.concatenate(
        [q[..., :n], rope.rotate(q[..., n:], positions, a.inv_freq(),
                                 interleaved=True)], axis=-1)


def _gated(o, gate):
    """Head i's output o [.., NH, Dv] times sigmoid(gate [.., NH])_i; a
    config without the gate hands None."""
    if gate is None:
        return o
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
        o.dtype)[..., None]


def _pieces(S: int, rows: int) -> int:
    if S % min(rows, S):
        raise ValueError(f"a prompt bucket of {S} rows is no multiple of the "
                         f"{rows} rows a prefill works through at once")
    return S // min(rows, S)


def _in_place(fn, x, rows: int):
    """x [S, ...] with ``fn`` applied to runs of ``rows`` rows, one after
    another, each run written back where it was read: one buffer of x's size
    however many runs. ``fn(piece, first row) -> (piece', COUNTS)``; the
    tallies are summed."""
    n = _pieces(x.shape[0], rows)
    rows = x.shape[0] // n

    def one(i, carry):
        x, extra = carry
        first = i * rows
        # behind a barrier, or the compiler hoists a norm's float32 copy of
        # ALL rows out of the loop
        piece, e = fn(jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(x, first, rows)), first)
        return (jax.lax.dynamic_update_slice_in_dim(x, piece, first, 0),
                extra + e)

    return jax.lax.fori_loop(0, n, one, (x, NO_COUNTS))


def _prefill_attention(x, w: dict, c: SparseLatentMoEConfig,
                       a: LatentAttention):
    """x [S, H] of one prompt -> (x + Attn(N1 x), what the layer caches of
    every row: ``{"ckv", "kidx"}`` [S, width] of a full layer, ``{"wckv"}``
    of a window layer). Two passes over the rows in chunks, so that nothing
    of x's size is made: the first leaves what every later query needs of
    every row (the query latent, the cached rows, the indexer's weights, the
    gate); the second attends a chunk of queries against them and adds to x
    in place: a full layer's chunk under its selection's mask against every
    key before it, a window layer's against the band it may see."""
    S = x.shape[0]
    NH, G, R = a.num_heads, a.head_group, a.kv_lora_rank
    Dv, Dr = a.v_head_dim, a.qk_rope_head_dim
    n = _pieces(S, ATTN_CHUNK)
    rows = S // n

    def project(args):
        piece, first = args
        at = first + jnp.arange(rows)
        h = rms_norm(jax.lax.optimization_barrier(piece), w["norm1"],
                     c.rms_norm_eps)
        cq, row = _latents(h, w, c, a, at)
        out = {"cq": cq, "row": row}
        if not a.window:
            out.update(ki=_index_key(h, w, c, at), wts=mm(h, w["wi_w"]))
        if c.head_gate:
            out["gate"] = mm(h, w["wg"])
        return out

    made = jax.tree.map(
        lambda t: t.reshape(S, *t.shape[2:]),
        jax.lax.map(project, (x.reshape(n, rows, -1),
                              jnp.arange(n, dtype=jnp.int32) * rows)))
    cq, row = made["cq"], made["row"]
    # a group of heads' columns at a time, the groups on a leading axis
    wq_b = jnp.swapaxes(w["wq_b"].reshape(-1, NH // G, G * a.head_dim), 0, 1)
    wk = w["wkv_bk"].reshape(NH // G, G, -1, R)
    wv = w["wkv_bv"].reshape(NH // G, G, R, Dv)
    latent, kr = row[:, :R], row[:, R:R + Dr]
    rows = S // _pieces(S, WINDOW_CHUNK if a.window else ATTN_CHUNK)
    # a window layer's band: the ``back`` rows before a chunk's first and the
    # chunk's own, out of arrays that hold ``back`` rows of nothing before
    # position 0 (whole lanes of rows, so that a band starts on a tile)
    back = -(-(a.window - 1) // sa.LANES) * sa.LANES if a.window else 0
    if back:
        latent, kr = (jnp.pad(t, ((back, 0), (0, 0))) for t in (latent, kr))

    def chunk(xq, first):
        def part(t):
            return jax.lax.dynamic_slice_in_dim(t, first, rows, axis=0)

        at = first + jnp.arange(rows)
        if a.window:
            lat, rot = (jax.lax.dynamic_slice_in_dim(t, first, rows + back)
                        for t in (latent, kr))
            behind = at[:, None] - (first - back + jnp.arange(rows + back))
            # a row of padding lies further back than any window reaches
            see = (behind >= 0) & (behind < a.window) & (behind <= at[:, None])

            @jax.named_scope("window_latent_attention")
            def attend(q, k, v):
                s = jnp.einsum("qhd,hkd->hqk", q, k,
                               preferred_element_type=jnp.float32)
                p = jax.nn.softmax(jnp.where(
                    see[None], s * a.softmax_scale, sa.NEG_INF), axis=-1)
                return jnp.einsum("hqk,hkd->qhd", p.astype(v.dtype), v)
        else:
            lat, rot = latent, kr
            qi, wi = _index_query(part(cq), part(made["wts"]), w, c, at)
            with jax.named_scope("sparse_select"):
                mask = sa.select_rows(qi, wi, made["ki"], first,
                                      topk=c.index_topk)

            @jax.named_scope("latent_attention")
            def attend(q, k, v):
                return jnp.swapaxes(sa.masked_attention(
                    jnp.swapaxes(q, 0, 1), k, v, mask, first,
                    scale=a.softmax_scale), 0, 1)

        def group(_, ws):
            q = _queries(part(cq), ws[0], a, at, G)
            # the expanded form: each head's k_nope and v out of the latent
            k = jnp.concatenate(
                [jnp.einsum("sr,hdr->hsd", lat, ws[1]),
                 jnp.broadcast_to(rot[None], (G, *rot.shape))], axis=-1)
            v = jnp.einsum("sr,hrd->hsd", lat, ws[2])
            return None, attend(q, k, v)                # [rows, G, Dv]

        _, o = jax.lax.scan(group, None, (wq_b, wk, wv))
        o = _gated(jnp.moveaxis(o, 0, 1).reshape(rows, NH, Dv),
                   part(made["gate"]) if c.head_gate else None)
        return xq + mm(o.reshape(rows, NH * Dv), w["wo"]), NO_COUNTS

    x, _ = _in_place(chunk, x, rows)
    if a.window:
        return x, {"wckv": row}
    return x, {"ckv": row, "kidx": made["ki"]}


def _mlp(x, w: dict, c: SparseLatentMoEConfig, counted):
    """The dense SwiGLU or the expert layer, by the leaves the layer has, for
    x [N, H]; returns (x', the expert layer's COUNTS)."""
    h = rms_norm(x, w["norm2"], c.rms_norm_eps)
    if "router" not in w:
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), NO_COUNTS
    m, tally = expert_layer_counts(
        h, w, experts_per_token=c.experts_per_token,
        experts_held=c.experts_held, route_norm=c.route_norm,
        route_scale=c.route_scale, groups=c.n_group,
        groups_kept=c.topk_group, counted=counted)
    return x + m, tally


def _head(params, c: SparseLatentMoEConfig, x):
    with jax.named_scope("lm_head"):
        return mm(rms_norm(x, params["final_norm"], c.rms_norm_eps),
                  params["lm_head"]).astype(jnp.float32)


def _through_layers(params: Params, x, layer):
    """x through the layers, one after another. ``layer(x, w, number) ->
    (x', the rows it caches by name, sums)``; ``number`` is the layer's place
    in the model. Returns (x, each named row stacked over the layers that
    cache it, in their order, the sums added up)."""
    rows, sums = {}, 0
    for number, w in enumerate(params["layers"]):
        x, made, s = layer(x, w, number)
        sums = sums + s
        for name, row in made.items():
            rows.setdefault(name, []).append(row)
    return x, {name: jnp.stack(r) for name, r in rows.items()}, sums


def _counters(c: SparseLatentMoEConfig, counted, tally, selected=0, live=0,
              window=(0, 0)):
    tokens = jnp.sum(counted, dtype=jnp.int32) * (
        c.num_layers - c.num_dense_layers)
    out = [tokens * c.experts_per_token, *tally, tokens, jnp.int32(selected),
           jnp.int32(live)]
    if c.layers_of(SLIDING):        # WINDOW_COUNTERS
        out += [jnp.int32(n) for n in window]
    return jnp.stack(out)


def prefill(params: Params, cfg: SparseLatentMoEConfig, tokens: jnp.ndarray,
            length) -> tuple[jnp.ndarray, dict, jnp.ndarray]:
    """tokens [1, S] (``length`` of them real) -> (float32 logits [V] of the
    last real position, the block: ``{"ckv", "kidx"}`` [full layers, 1, S,
    width] and, of window layers, ``{"wckv"}`` [window layers, 1, S, width]
    (every row of the prompt: ``kv_kinds.insert`` takes the ring's), the
    counters ``counters(cfg)`` names)."""
    c = cfg
    S = tokens.shape[1]
    counted = jnp.arange(S) < length

    def layer(x, w, number):
        x, made = _prefill_attention(x, w, c, c.attention(number))

        def mlp(piece, first):
            return _mlp(piece, w, c, jax.lax.dynamic_slice_in_dim(
                counted, first, piece.shape[0]))

        x, tally = _in_place(mlp, x, MLP_ROWS)
        return x, made, tally

    x, rows, tally = _through_layers(
        params, embed(params, tokens, c.dtype)[0], layer)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, keepdims=True)
    return (_head(params, c, last)[0],
            {name: r[:, None] for name, r in rows.items()},
            _counters(c, counted, tally))


def decode(params: Params, cfg: SparseLatentMoEConfig, tokens: jnp.ndarray,
           cache: kv_kinds.LayeredKV, kinds, active: jnp.ndarray):
    """One token a slot against the VIEW of the held cache (``kv_kinds``):
    tokens [B] at positions ``cache.lengths`` -> (float32 logits [B, V], this
    step's rows by name, [the name's layers, B, 1, width], the counters over
    the ``active`` slots). The cache is read, never written: the caller
    appends, and the step's own row takes part in its selection and its
    attention without having been written."""
    c = cfg
    B = tokens.shape[0]
    lengths = cache.lengths
    positions = lengths[:, None]
    # a layer's place in the stack of its kind, and that kind's held arrays
    place = {number: (n, held) for kd, held in zip(kinds, cache.held)
             for n, number in enumerate(kd.layers)}
    # a slot that is not active reads nothing (its output is dropped: the
    # engine keeps such a slot's token)
    reads = jnp.where(active, lengths, 0)

    def layer(x, w, number):
        a = c.attention(number)
        index, held = place[number]
        NH, R, Dn = a.num_heads, a.kv_lora_rank, a.qk_nope_head_dim
        h = rms_norm(x, w["norm1"], c.rms_norm_eps)
        cq, row = _latents(h, w, c, a, positions)
        q = _queries(cq, w["wq_b"], a, positions, NH)[:, 0]     # [B, NH, Dk]
        # the absorbed query: each head's q_nope through its Wkv_b^K, then
        # the rotated part, against a latent row [c | kr | 0]
        q = jnp.concatenate(
            [jnp.einsum("bhd,hdr->bhr", q[..., :Dn], w["wkv_bk"]),
             q[..., Dn:],
             jnp.zeros((B, NH, a.latent_width - R - a.qk_rope_head_dim),
                       q.dtype)], axis=-1)
        if a.window:
            made = {"wckv": row}
            picked = jnp.int32(0)
            mix = sa.window_decode_attention(
                q, row[:, 0], held["wckv"], index, reads, window=a.window,
                scale=a.softmax_scale, value_dim=R)
        else:
            ki = _index_key(h, w, c, positions)[:, 0]
            qi, wts = _index_query(cq, mm(h, w["wi_w"]), w, c, positions)
            qi, wts = qi[:, 0], wts[:, 0]
            with jax.named_scope("indexer"):
                scores = sa.decode_index_scores(qi, wts, held["kidx"], index,
                                                reads)
                own = jnp.einsum(
                    "bh,bh->b", wts, jnp.maximum(jnp.einsum(
                        "bhd,bd->bh", qi, ki,
                        preferred_element_type=jnp.float32), 0.0))
            made = {"ckv": row, "kidx": ki[:, None]}
            mix, kept = sa.decode_attention(
                q, row[:, 0], own, scores, held["ckv"], index, reads,
                topk=c.index_topk, scale=a.softmax_scale, value_dim=R)
            picked = jnp.sum(jnp.where(active, kept, 0), dtype=jnp.int32)
        o = _gated(jnp.einsum("bhr,hrd->bhd", mix, w["wkv_bv"]),
                   mm(h, w["wg"])[:, 0] if c.head_gate else None)
        x = x + mm(o.reshape(B, 1, NH * a.v_head_dim), w["wo"])
        x, tally = _mlp(x[:, 0], w, c, active)
        return x[:, None], made, jnp.append(tally, picked)

    x, rows, sums = _through_layers(
        params, embed(params, tokens[:, None], c.dtype), layer)

    def over_active(n):
        return jnp.sum(jnp.where(active, n, 0), dtype=jnp.int32)

    behind = c.sliding.window - 1 if c.layers_of(SLIDING) else 0
    return (_head(params, c, x)[:, 0], rows, _counters(
        c, active, sums[:-1], sums[-1],
        over_active(lengths + 1) * len(c.layers_of(FULL)),
        (over_active(jnp.minimum(lengths, behind) + 1)
         * len(c.layers_of(SLIDING)),
         over_active(lengths + 1) * len(c.layers_of(SLIDING)))))
