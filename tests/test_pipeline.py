"""Pipeline parallelism (GPipe over the ``pipe`` mesh axis): numerics parity
with the plain forward, composition with data parallelism, and the
differentiable train step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.parallel.pipeline import (
    make_pp_train_step,
    pipeline_forward,
    pp_specs_for_params,
)


@pytest.fixture(scope="module")
def model4():
    cfg = dataclasses.replace(llama.llama_tiny(), num_layers=4)
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def _shard_pp(params, mesh):
    specs = pp_specs_for_params(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda v: isinstance(v, P),
    )


def test_pipeline_matches_plain_forward(model4):
    """pipe=4 x data=2 pipeline forward == unsharded llama.forward."""
    cfg, params = model4
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    want, _ = llama.forward(params, cfg, tokens, positions)

    mesh = make_mesh(pipe=4, data=2)
    sharded = _shard_pp(params, mesh)
    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda p, t, pos: pipeline_forward(
                p, cfg, t, pos, mesh=mesh, num_microbatches=4
            )
        )(sharded, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_single_stage_degenerates(model4):
    """pipe=1 must equal the plain forward exactly (no schedule effects)."""
    cfg, params = model4
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    want, _ = llama.forward(params, cfg, tokens, positions)

    mesh = make_mesh(pipe=1, data=8)
    sharded = _shard_pp(params, mesh)
    with jax.set_mesh(mesh):
        got = jax.jit(
            lambda p, t, pos: pipeline_forward(
                p, cfg, t, pos, mesh=mesh, num_microbatches=2
            )
        )(sharded, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_validations(model4):
    cfg, params = model4
    mesh = make_mesh(pipe=4, data=2)
    tokens = jnp.zeros((4, 8), jnp.int32)
    positions = jnp.zeros((4, 8), jnp.int32)
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="microbatches"):
            pipeline_forward(params, cfg, tokens, positions, mesh=mesh,
                             num_microbatches=3)
        bad_cfg = dataclasses.replace(cfg, num_layers=3)
        with pytest.raises(ValueError, match="pipe"):
            pipeline_forward(params, bad_cfg, tokens, positions, mesh=mesh)


def test_pp_train_step_learns(model4):
    """Two pp train steps: loss finite and decreasing on a repeated batch
    (backward through the ppermute ring works)."""
    import optax

    from kukeon_tpu.training import create_train_state
    from kukeon_tpu.training.train_step import make_optimizer

    cfg, _ = model4
    mesh = make_mesh(pipe=4, data=2)
    with jax.set_mesh(mesh):
        optimizer = make_optimizer(learning_rate=1e-2, warmup_steps=1,
                                   total_steps=10)
        state, optimizer = create_train_state(
            cfg, mesh, jax.random.key(0), optimizer,
            init_fn=lambda k: llama.init_params(k, cfg),
            specs=pp_specs_for_params(
                jax.eval_shape(lambda k: llama.init_params(k, cfg),
                               jax.random.key(0))
            ),
        )
        step = make_pp_train_step(cfg, mesh, optimizer, num_microbatches=4)
        B, S = 8, 16
        tokens = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)
        mask = jnp.ones((B, S), jnp.float32)
        state, loss0 = step(state, tokens, targets, mask)
        state, _ = step(state, tokens, targets, mask)   # warmup step: lr ~ 0
        state, loss2 = step(state, tokens, targets, mask)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss0)
    assert int(state.step) == 3
