"""Pipeline parallelism: GPipe microbatch schedule over the ``pipe`` axis.

The last of the canonical parallelism dimensions (tp/dp/fsdp/sp/ep live in
sharding.py / ring_attention.py / moe.py). Layer-stacked weights ([L, ...]
leading axis) shard their L axis over ``pipe`` — stage s owns layers
[s*L/P, (s+1)*L/P) with no weight re-layout — and activations hop stage to
stage via ``lax.ppermute`` under a ``shard_map`` that is manual over *only*
the pipe axis (``axis_names={'pipe'}``): tensor/fsdp/data sharding inside a
stage stays GSPMD-automatic, so pp composes with tp/dp.

Schedule: plain GPipe. M microbatches flow through P stages in M+P-1 ticks;
each tick every stage runs its local layer scan, the last stage banks its
finished microbatch, and the ring rotates. Bubble fraction is (P-1)/(M+P-1)
— pick M >= 4*P for ~80%+ utilization. The tick loop is a static-bound
``fori_loop`` (reverse-differentiable), so the same forward drives training.

Design notes, TPU-first:
- ``pipe`` is the OUTERMOST mesh axis: stage hops are low-frequency,
  latency-tolerant point-to-point transfers — exactly what DCN (multi-host)
  or the outer ICI dimension should carry, while tensor collectives stay on
  the inner ring.
- Activations are [Bm, S, H] per tick — the only cross-stage traffic.
  Weights never move.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kukeon_tpu.models import llama
from kukeon_tpu.parallel.mesh import (
    AXIS_PIPE,
)
from kukeon_tpu.parallel import sharding as shd


def pp_param_specs(fsdp: bool = False) -> dict:
    """Llama param specs with the stacked-layer axis sharded over ``pipe``.

    Embedding / final norm / lm_head are replicated across stages (first and
    last stage use them; they are small next to the layer stack)."""
    specs = shd.llama_param_specs(fsdp)
    layers = {}
    for name, spec in specs["layers"].items():
        layers[name] = P(AXIS_PIPE, *spec[1:])
    specs["layers"] = layers
    return specs


def pp_specs_for_params(params, fsdp: bool = False) -> dict:
    full = pp_param_specs(fsdp)
    return {k: full[k] for k in params}


def pipeline_forward(
    params: dict,
    cfg: llama.LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    mesh: Mesh | None = None,
    num_microbatches: int | None = None,
    attn_impl: str = "auto",
) -> jnp.ndarray:
    """Pipeline-parallel forward: logits [B, S, V] f32.

    ``tokens``/``positions`` are [B, S] with B divisible by
    ``num_microbatches`` (default: 2 * pipe size). The layer weights must be
    sharded with :func:`pp_param_specs`. No KV-cache path: pipelining is the
    training/prefill layout; decode serving uses the tensor-parallel engine.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    P_ = mesh.shape.get(AXIS_PIPE, 1)
    c = cfg
    B, S = tokens.shape
    if c.num_layers % P_:
        raise ValueError(f"num_layers {c.num_layers} % pipe {P_} != 0")
    M = num_microbatches or max(2 * P_, 1)
    if B % M:
        raise ValueError(f"batch {B} % microbatches {M} != 0")
    Bm = B // M

    x = llama._embed(params, tokens, c.dtype)          # [B, S, H]
    H = x.shape[-1]
    xm = x.reshape(M, Bm, S, H)
    pos_m = positions.reshape(M, Bm, S)

    def stages(layer_ws, xm, pos_m):
        """Manual over ``pipe`` only: layer_ws leaves arrive [L/P, ...]."""
        stage = jax.lax.axis_index(AXIS_PIPE)
        perm = [(i, (i + 1) % P_) for i in range(P_)]

        def run_local(state, pstate):
            def body(carry, w):
                return llama.transformer_block(
                    carry, w, c, pstate, attn_impl=attn_impl
                ), None

            out, _ = jax.lax.scan(body, state, layer_ws)
            return out

        state = jnp.zeros((Bm, S, H), c.dtype)
        pstate = jnp.zeros((Bm, S), jnp.int32)
        out = jnp.zeros((M, Bm, S, H), c.dtype)
        # Mark device-dependent so the loop carry type is stable.
        state = jax.lax.pcast(state, (AXIS_PIPE,), to="varying")
        pstate = jax.lax.pcast(pstate, (AXIS_PIPE,), to="varying")
        out = jax.lax.pcast(out, (AXIS_PIPE,), to="varying")

        def tick(t, carry):
            state, pstate, out = carry
            feed_idx = jnp.minimum(t, M - 1)
            inject = jax.lax.dynamic_index_in_dim(xm, feed_idx, 0, keepdims=False)
            pinject = jax.lax.dynamic_index_in_dim(pos_m, feed_idx, 0, keepdims=False)
            feeding = jnp.logical_and(stage == 0, t < M)
            state = jnp.where(feeding[..., None, None, None], inject, state)
            pstate = jnp.where(feeding[..., None, None], pinject, pstate)

            state = run_local(state, pstate)

            # Last stage banks microbatch t-(P-1) once the pipe is full.
            emit_idx = t - (P_ - 1)
            banked = jax.lax.dynamic_update_slice(
                out, state[None].astype(out.dtype),
                (jnp.maximum(emit_idx, 0), 0, 0, 0),
            )
            emit = jnp.logical_and(stage == P_ - 1, emit_idx >= 0)
            out = jnp.where(emit[..., None, None, None, None], banked, out)

            state = jax.lax.ppermute(state, AXIS_PIPE, perm)
            pstate = jax.lax.ppermute(pstate, AXIS_PIPE, perm)
            return state, pstate, out

        _, _, out = jax.lax.fori_loop(0, M + P_ - 1, tick, (state, pstate, out))
        # Only the last stage holds real outputs; psum replicates them
        # (every other stage contributes zeros).
        mask = (stage == P_ - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, AXIS_PIPE)

    layer_in_specs = jax.tree.map(
        lambda _: P(AXIS_PIPE), params["layers"],
        is_leaf=lambda v: isinstance(v, (jnp.ndarray, jax.Array)) or hasattr(v, "shape"),
    )
    out_m = jax.shard_map(
        stages,
        mesh=mesh,
        in_specs=(layer_in_specs, P(), P()),
        out_specs=P(),
        axis_names={AXIS_PIPE},
    )(params["layers"], xm, pos_m)

    x = out_m.reshape(B, S, H)
    x = llama.rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return llama._logits(params, c, x)


def make_pp_train_step(cfg, mesh: Mesh, optimizer, *,
                       num_microbatches: int | None = None):
    """Jitted, donated pipeline-parallel train step (GPipe forward; reverse
    AD runs the schedule backwards — ppermute transposes to the reverse
    ring). Composes with tensor/data sharding via the auto axes."""
    import optax

    from kukeon_tpu.training.train_step import TrainState, cross_entropy_loss

    def loss_fn(p, tokens, targets, mask, positions):
        logits = pipeline_forward(
            p, cfg, tokens, positions,
            mesh=mesh, num_microbatches=num_microbatches,
        )
        return cross_entropy_loss(logits, targets, mask)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state, tokens, targets, mask):
        B, S = tokens.shape
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, tokens, targets, mask, positions
        )
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=new_params, opt_state=new_opt,
                       step=state.step + 1),
            loss,
        )

    return train_step
