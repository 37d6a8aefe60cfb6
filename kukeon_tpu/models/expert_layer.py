"""An expert layer that is told which experts it holds.

The router scores every token against ALL ``num_experts`` and takes its top
k; this chip computes the part its ``experts_held = (first, count)`` give.
Eight such shares (and the shared expert, once) sum to the uncut layer, which
is what an expert-parallel deployment's combine adds up; on one chip the
exchange is simply absent. No capacity and no dropped token: the (token,
choice) pairs are sorted by expert, and only the rows that count have a
group: the pairs of an idle slot and of a prompt's padding join the pairs that
chose an expert held elsewhere, past the last group, so a step reads the held
experts its active slots reach and no other. What is walked is the FRONT of
that order alone: blocks of ``block_rows`` sorted rows, as many as the pairs
in a group fill, a trip count the program reads from its own data. A block
gathers its rows of h, runs them through the three products over the held
stack with the groups clipped to the block, and adds each row, weighted,
to its token's float32 output. Beside the index vectors of the sort nothing
of ``tokens x top-k`` rows is made: what is allocated is a block and the
[N, H] output.

What runs the three products is chosen from what the call observes
(``kernel_runs``; counted as ``kukeon_op_impl_traces_total{op=
"expert_products"}``): on one TPU, with widths in lane tiles, the Pallas
kernels of ``ops/expert_products.py``, two calls a block (``gate_up`` reads
both stacks and emits ``silu(x Wg) * (x Wu)``, ``down`` the third product),
each a walk over the experts that HAVE a row in the block with every reached
expert's matrix streamed once; on any other backend, and on a mesh of several
devices, ``jax.lax.ragged_dot``, three calls a block. The roundings are the
same in both (a product rounds to the activations' dtype, ``silu`` runs in
float32), the counts are the same counts.

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

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kukeon_tpu.models.llama import mm
from kukeon_tpu.ops import dispatch
from kukeon_tpu.ops import expert_products as products


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
# ... and after them the (token, choice) pairs the call made and the sorted
# rows its products worked over: whole blocks, as many as the held pairs fill.
PAIR_ROWS = ("kukeon_moe_pair_rows_total", "kukeon_moe_pair_rows_worked_total")
COUNTS = TALLY + PAIR_ROWS
NO_COUNTS = np.zeros(len(COUNTS), np.int32)

BLOCK_ROWS = 2048


def block_rows(pairs: int) -> int:
    """The sorted rows ``_routed`` works through at once, of ``pairs`` a
    call: all of a decode step's few hundred, 2048 of a prefill piece's (on
    the chip 1024 to 4096 rows cost the same to within 3% at every cell's
    widths and held share; PERF.md section 6, PR 48)."""
    return min(pairs, BLOCK_ROWS)


def kernel_runs(rows: int, H: int, I: int, dtype, devices: int) -> bool:
    """Whether a block's three products run as the Pallas kernels of
    ``ops/expert_products.py`` (else as ``jax.lax.ragged_dot``): a TPU, no
    ambient mesh or one of a single device (GSPMD does not partition a
    ``pallas_call``), and shapes the kernel tiles."""
    return (jax.default_backend() == "tpu" and devices <= 1
            and products.supports(rows, H, I, dtype)
            and products.supports(rows, I, H, dtype))


@functools.partial(jax.jit, static_argnames=("rows", "kernel"))
def _routed(h, e_gate, e_up, e_down, local, held, wts, *, rows: int,
            kernel: bool = False):
    """The held experts' part for h [N, H] in float32, the held experts
    reached and the rows worked over. The (token, choice) pairs are sorted by
    held expert, every other pair last, in no group (it chose an expert held
    elsewhere, or its token does not count), and only the FRONT of that order
    is walked: blocks of ``rows`` rows, as many as the ``held`` pairs fill, a
    count the program reads from its own data (where one block holds every
    pair of the call, that block once and no loop). A block gathers its rows
    of h, runs the three products over the held stacks with the groups
    clipped to it (``kernel``: the two Pallas calls of
    ``ops/expert_products.py``; else three ``jax.lax.ragged_dot``), and adds
    each row, weighted, to its token's output (a product with the
    block's [N, rows] weighted one-hot: float32 sums on the MXU, no scatter).
    Nothing of ``N * K`` rows is made but index vectors; with no held pair
    the loop runs zero times and every token gets zeros.

    Jitted on its own: a family that unrolls its layers traces and lowers
    this body once a program, not once a layer, and a boot's second pass over
    a program finds it traced."""
    N, K = local.shape
    count = e_gate.shape[0]
    flat = jnp.where(held, local, count).reshape(N * K)
    order = jnp.argsort(flat)                       # stable: by expert
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    blocks = (ends[-1] + rows - 1) // rows
    # whole blocks to slice: a pad row lies past the last group
    order = jnp.pad(order, (0, -(N * K) % rows))
    weight = jnp.take(wts.reshape(N * K), order).astype(h.dtype)

    def block(i, out):
        first = i * rows
        pairs = jax.lax.dynamic_slice_in_dim(order, first, rows)
        in_group = first + jnp.arange(rows) < ends[-1]
        tokens = pairs // K
        xs = jnp.take(h, tokens, axis=0)            # [rows, H]
        # the block's part of each group: its rows are offsets[e]:offsets[e+1]
        offsets = jnp.clip(starts - first, 0, rows)
        if kernel:
            y = products.down(products.gate_up(xs, e_gate, e_up, offsets),
                              e_down, offsets)
        else:
            clipped = jnp.diff(offsets)
            gate = jax.nn.silu(jax.lax.ragged_dot(xs, e_gate, clipped)
                               .astype(jnp.float32)).astype(h.dtype)
            up = jax.lax.ragged_dot(xs, e_up, clipped)
            y = jax.lax.ragged_dot(gate * up, e_down, clipped)
        # Rows past the last group belong to no expert; what a kernel leaves
        # there is not defined, so they are selected out, not multiplied out.
        y = jnp.where(in_group[:, None], y, 0)
        to_token = jnp.where(
            (tokens == jnp.arange(N)[:, None]) & in_group,
            jax.lax.dynamic_slice_in_dim(weight, first, rows), 0)
        return out + jnp.dot(to_token, y, preferred_element_type=jnp.float32)

    out = jnp.zeros(h.shape, jnp.float32)
    if rows == N * K:
        # One block holds every pair (a decode step's, a short bucket's): it
        # is walked once whatever it holds, with no loop. A loop nested in a
        # decode step's keeps the compiler from appending to window_moe's
        # held full-attention stack in place: two copies of 537 MB a step at
        # Trinity's sizes (PERF.md section 6, PR 48).
        blocks = 1
        out = block(0, out)
    else:
        out = jax.lax.fori_loop(0, blocks, block, out)
    return out, jnp.sum(sizes > 0, dtype=jnp.int32), blocks * rows


def expert_layer_counts(h: jnp.ndarray, w: dict, *, experts_per_token: int,
                        experts_held: tuple[int, int],
                        route_norm: bool = True, route_scale: float = 1.0,
                        groups: int = 1, groups_kept: int = 1,
                        scoring: str = SIGMOID, counted: jnp.ndarray,
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """h [..., H] -> (y [..., H], COUNTS int32 [5]): the shared expert once
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
    rows = block_rows(held.size)
    kernel = (w["e_gate"].dtype == x.dtype and kernel_runs(
        rows, H, w["e_gate"].shape[2], x.dtype,
        jax.sharding.get_abstract_mesh().size))
    dispatch.note("expert_products", "pallas" if kernel else "xla")
    with jax.named_scope("expert_layer"):
        y, reached, worked = _routed(x, w["e_gate"], w["e_up"], w["e_down"],
                                     local, held, wts, rows=rows,
                                     kernel=kernel)
    with jax.named_scope("shared_expert"):
        y = (y + swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
             ).astype(h.dtype)
    return y.reshape(*lead, H), jnp.stack(
        [jnp.sum(held, dtype=jnp.int32), jnp.int32(count), reached,
         jnp.int32(held.size), jnp.int32(worked)])
