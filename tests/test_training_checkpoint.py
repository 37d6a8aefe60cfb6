"""Training checkpoint/resume: sharded save -> restore into the resuming
mesh's layout (incl. a DIFFERENT mesh), training continues bit-identical."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.training import (
    create_train_state,
    latest_step,
    make_train_step,
    restore_checkpoint,
    save_checkpoint,
)
from kukeon_tpu.training.train_step import make_optimizer


def _batch(cfg, mesh, batch_sharding, B=4, S=32):
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size),
        batch_sharding,
    )
    return tokens, jnp.roll(tokens, -1, axis=1), jax.device_put(
        jnp.ones((B, S), jnp.float32), batch_sharding)


def test_save_restore_resume_identical(tmp_path):
    cfg = llama.llama_tiny()
    mesh = make_mesh(tensor=2, fsdp=2, data=2)
    root = str(tmp_path / "ckpts")
    with jax.set_mesh(mesh):
        opt = make_optimizer(warmup_steps=1, total_steps=10)
        state, opt = create_train_state(cfg, mesh, jax.random.key(0), opt)
        step_fn, bsh = make_train_step(cfg, mesh, opt)
        tokens, targets, mask = _batch(cfg, mesh, bsh)
        state, _ = step_fn(state, tokens, targets, mask)

        save_checkpoint(root, state)
        assert latest_step(root) == 1

        # Continue the ORIGINAL run one more step -> reference.
        ref_state, ref_loss = step_fn(state, tokens, targets, mask)

    # Resume in a "fresh job": new state tree on the same mesh, restored.
    with jax.set_mesh(mesh):
        fresh, opt2 = create_train_state(cfg, mesh, jax.random.key(9), opt)
        restored = restore_checkpoint(root, fresh)
        assert int(restored.step) == 1
        step2, bsh2 = make_train_step(cfg, mesh, opt2)
        tokens, targets, mask = _batch(cfg, mesh, bsh2)
        got_state, got_loss = step2(restored, tokens, targets, mask)

    assert float(got_loss) == float(ref_loss)
    for a, b in zip(jax.tree.leaves(got_state.params),
                    jax.tree.leaves(ref_state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_onto_different_mesh(tmp_path):
    """A checkpoint written under tensor=2/fsdp=2 restores onto a
    tensor=4/data=2 mesh — resharding is transparent (the abstract target
    carries the new shardings)."""
    cfg = llama.llama_tiny()
    root = str(tmp_path / "ckpts")
    mesh_a = make_mesh(tensor=2, fsdp=2, data=2)
    with jax.set_mesh(mesh_a):
        opt = make_optimizer(warmup_steps=1, total_steps=10)
        state, opt = create_train_state(cfg, mesh_a, jax.random.key(0), opt)
        save_checkpoint(root, state)
        want = [np.asarray(x) for x in jax.tree.leaves(state.params)]

    mesh_b = make_mesh(tensor=4, data=2)
    with jax.set_mesh(mesh_b):
        fresh, _ = create_train_state(cfg, mesh_b, jax.random.key(7), opt)
        restored = restore_checkpoint(root, fresh)
        got = [np.asarray(x) for x in jax.tree.leaves(restored.params)]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_latest_step_empty_and_missing(tmp_path):
    assert latest_step(str(tmp_path / "nope")) is None
    (tmp_path / "c").mkdir()
    assert latest_step(str(tmp_path / "c")) is None


@pytest.mark.faults
def test_interrupted_save_preserves_previous_checkpoint(tmp_path):
    """A save killed between writing and publishing (fault seam
    ``checkpoint.save`` = SIGKILL mid-save) must leave the PREVIOUS
    checkpoint as the newest complete one: latest_step never sees the
    partial write, restore still succeeds, and a later healthy save of the
    same step goes through."""
    import dataclasses
    import os

    from kukeon_tpu import faults

    cfg = llama.llama_tiny()
    mesh = make_mesh(tensor=2, data=4)
    root = str(tmp_path / "ckpts")
    with jax.set_mesh(mesh):
        opt = make_optimizer(warmup_steps=1, total_steps=10)
        state, opt = create_train_state(cfg, mesh, jax.random.key(0), opt)
        save_checkpoint(root, state)                    # step 0: the survivor
        assert latest_step(root) == 0
        want = [np.asarray(x) for x in jax.tree.leaves(state.params)]

        bumped = dataclasses.replace(state, step=state.step + 1)
        os.environ[faults.ENV] = "checkpoint.save:1:1"
        with pytest.raises(faults.FaultInjected):
            save_checkpoint(root, bumped)               # killed mid-save

        # The interrupted write published nothing and left no debris that
        # a resume would mistake for a checkpoint.
        assert latest_step(root) == 0
        assert sorted(os.listdir(root)) == ["step_00000000"]

        restored = restore_checkpoint(root, state)
        assert int(restored.step) == 0
        got = [np.asarray(x) for x in jax.tree.leaves(restored.params)]
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

        # Fault exhausted (count=1): the retried save completes and wins.
        save_checkpoint(root, bumped)
        assert latest_step(root) == 1
