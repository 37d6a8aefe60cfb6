"""Sharded training: loss decreases, runs on fsdp×tensor and seq meshes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.training import create_train_state, make_train_step
from kukeon_tpu.training.train_step import make_optimizer


def _fake_batch(key, cfg, B, S):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
    return tokens, targets, mask


@pytest.mark.parametrize(
    "mesh_kw",
    [
        dict(fsdp=4, tensor=2),
        dict(data=2, seq=4),
    ],
    ids=["fsdp4_tp2", "dp2_sp4"],
)
def test_train_step_loss_decreases(mesh_kw):
    cfg = llama.llama_tiny()
    mesh = make_mesh(**mesh_kw)
    with jax.set_mesh(mesh):
        optimizer = make_optimizer(learning_rate=1e-2, warmup_steps=1, total_steps=100)
        state, optimizer = create_train_state(cfg, mesh, jax.random.key(0), optimizer)
        train_step, batch_sharding = make_train_step(cfg, mesh, optimizer)

        B, S = 8, 32
        tokens, targets, mask = _fake_batch(jax.random.key(1), cfg, B, S)
        tokens = jax.device_put(tokens, batch_sharding)
        targets = jax.device_put(targets, batch_sharding)
        mask = jax.device_put(mask, batch_sharding)

        losses = []
        for _ in range(5):
            state, loss = train_step(state, tokens, targets, mask)
            losses.append(float(loss))

    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 5
