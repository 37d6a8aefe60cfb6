"""An expert layer that is told which experts it holds.

The router scores every token against ALL ``num_experts`` and takes its top
k; this chip computes the part its ``experts_held = (first, count)`` give.
Eight such shares (and the shared expert, once) sum to the uncut layer, which
is what an expert-parallel deployment's combine adds up; on one chip the
exchange is simply absent. No capacity and no dropped token: the (token,
choice) pairs are sorted by expert and run through ``jax.lax.ragged_dot`` over
the held stack, which on the TPU visits only the row tiles and the experts
that have rows. Only the rows that count have any: the pairs of an idle slot
and of a prompt's padding join the pairs that chose an expert held elsewhere,
past the last group, so a step reads the held experts its active slots reach
and no other.

  s   = sigmoid(float32(h) Wr)
  sel = top_k(s + b)                         b: selection only
        (group-limited, ``groups`` > 1: the experts lie in ``groups`` equal
        runs, a run scores the sum of its two best s + b, and only the
        experts of the ``groups_kept`` best runs stand for selection)
  w   = s[sel] / (sum(s[sel]) + 1e-20) * route_scale       (``route_norm``)
  y   = Shared(h) + sum_{e in sel, e held} w_e Expert_e(h)           (SwiGLU)

``scoring="softmax_selected"`` is the other router (granitemoehybrid's): no
sigmoid, no bias, no scale; the top k of the LOGITS, weighted by a softmax
over those k logits alone:

  l = float32(h) Wr;   sel = top_k(l);   w = softmax(l[sel])

Weights ``w``: ``router`` [H, E] float32, ``bias`` [E] float32 (the sigmoid
router's; the other has none), ``e_gate`` /
``e_up`` [count, H, I], ``e_down`` [count, I, H], and the shared expert's
``s_gate`` / ``s_up`` [H, Is], ``s_down`` [Is, H].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kukeon_tpu.models.llama import mm


def select(biased: jnp.ndarray, k: int, groups: int = 1,
           groups_kept: int = 1) -> jnp.ndarray:
    """The ``k`` experts of largest biased score [N, E], among the experts of
    the ``groups_kept`` best runs where the experts lie in ``groups`` runs."""
    if groups > 1:
        N, E = biased.shape
        by_group = biased.reshape(N, groups, E // groups)
        best2, _ = jax.lax.top_k(by_group, 2)
        _, kept = jax.lax.top_k(best2.sum(axis=-1), groups_kept)
        stands = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
        biased = jnp.where(stands[:, :, None], by_group, -jnp.inf
                           ).reshape(N, E)
    return jax.lax.top_k(biased, k)[1]


SIGMOID, SOFTMAX_SELECTED = "sigmoid", "softmax_selected"


def route(h: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray | None,
          k: int, *, norm: bool = True, scale: float = 1.0, groups: int = 1,
          groups_kept: int = 1, scoring: str = SIGMOID,
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """h [N, H] -> (sel [N, k] int32, w [N, k] float32). The router runs in
    float32 at the highest precision: a selection should not turn on the
    activation dtype or on the TPU's default single bf16 pass."""
    logits = jnp.dot(h.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == SOFTMAX_SELECTED:
        top, sel = jax.lax.top_k(logits, k)
        return sel.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    s = jax.nn.sigmoid(logits)
    sel = select(s + bias, k, groups, groups_kept)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * scale


def swiglu(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    gate = jax.nn.silu(mm(h, w_gate).astype(jnp.float32)).astype(h.dtype)
    return mm(gate * mm(h, w_up), w_down)


# What a call of the layer sums beside its output, in this order: the counted
# choices that fell on a held expert, the experts it holds, and those of them
# whose group had a row (whose weights the three ragged products had to read).
TALLY = ("kukeon_moe_held_hits_total", "kukeon_moe_held_experts_total",
         "kukeon_moe_held_experts_reached_total")
NO_TALLY = np.zeros(len(TALLY), np.int32)


def _routed(h, w: dict, local, held, wts):
    """The held experts' part for h [N, H], and the held experts reached: the
    ``held`` (token, choice) pairs sorted by held expert, every other pair
    last, in no group (it chose an expert held elsewhere, or its token does
    not count); three ragged products over the held stack, weighted, and
    summed back per token. A token with no pair in a group gets zeros."""
    N, K = local.shape
    count = w["e_gate"].shape[0]
    flat = jnp.where(held, local, count).reshape(N * K)
    order = jnp.argsort(flat)                       # stable: by expert
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    xs = jnp.take(h, order // K, axis=0)            # [N*K, H]
    gate = jax.nn.silu(jax.lax.ragged_dot(xs, w["e_gate"], sizes)
                       .astype(jnp.float32)).astype(h.dtype)
    up = jax.lax.ragged_dot(xs, w["e_up"], sizes)
    y = jax.lax.ragged_dot(gate * up, w["e_down"], sizes)
    # Rows past the last group belong to no expert; what a kernel leaves
    # there is not defined, so they are selected out, not multiplied out.
    in_group = (jnp.take(flat, order) < count)[:, None]
    y = jnp.where(in_group, y * jnp.take(wts.reshape(N * K), order)[:, None]
                  .astype(y.dtype), 0)
    back = jnp.argsort(order)                       # pair -> its sorted row
    return (jnp.take(y, back, axis=0).reshape(N, K, -1).sum(axis=1),
            jnp.sum(sizes > 0, dtype=jnp.int32))


def expert_layer(h: jnp.ndarray, w: dict, *, experts_per_token: int,
                 experts_held: tuple[int, int], route_norm: bool = True,
                 route_scale: float = 1.0, groups: int = 1,
                 groups_kept: int = 1, scoring: str = SIGMOID,
                 counted: jnp.ndarray,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """h [..., H] -> (y [..., H], TALLY int32 [3]): the shared expert once
    plus the held experts' share of the routed sum. ``counted`` [...] bool
    marks the tokens that are read afterwards (real prompt tokens, active
    slots): only their choices are hits and only their pairs are routed, so a
    held expert that no counted token chose is not read. Any other token gets
    the shared expert alone back; a counted token's output does not depend on
    which other tokens count."""
    lead, H = h.shape[:-1], h.shape[-1]
    x = h.reshape(-1, H)
    first, count = experts_held
    with jax.named_scope("router"):
        sel, wts = route(x, w["router"], w.get("bias"), experts_per_token,
                         norm=route_norm, scale=route_scale, groups=groups,
                         groups_kept=groups_kept, scoring=scoring)
        local = sel - first
        held = (local >= 0) & (local < count) & counted.reshape(-1, 1)
    with jax.named_scope("expert_layer"):
        y, reached = _routed(x, w, local, held, wts)
    with jax.named_scope("shared_expert"):
        y = y + swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    return y.reshape(*lead, H), jnp.stack(
        [jnp.sum(held, dtype=jnp.int32), jnp.int32(count), reached])
