"""Token-dataset loader: determinism (resume alignment), sharded placement,
and an end-to-end train loop over real data with checkpoint resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.training import (
    TokenDataset,
    batches,
    create_train_state,
    make_train_step,
    restore_checkpoint,
    sample_batch,
    save_checkpoint,
)
from kukeon_tpu.training.train_step import make_optimizer


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("data") / "toks.bin")
    rng = np.random.default_rng(0)
    return TokenDataset.write(path, rng.integers(0, 512, size=50_000))


def test_write_read_roundtrip(tmp_path):
    ds = TokenDataset.write(str(tmp_path / "t.bin"), np.arange(1000) % 512)
    assert len(ds) == 1000
    assert ds.tokens.dtype == np.uint16
    big = TokenDataset.write(str(tmp_path / "b.bin"), np.array([70_000, 3]))
    assert big.tokens.dtype == np.uint32


def test_batches_deterministic_and_resumable(dataset):
    """Batch at step N is a pure function of (seed, N): restarting the
    iterator at step 2 reproduces the original schedule exactly."""
    run1 = [t for _, t, _, _ in batches(dataset, 4, 64, num_steps=4, seed=7)]
    run2 = [t for _, t, _, _ in batches(dataset, 4, 64, start_step=2,
                                        num_steps=2, seed=7)]
    np.testing.assert_array_equal(run1[2], run2[0])
    np.testing.assert_array_equal(run1[3], run2[1])
    # Different seed -> different schedule.
    other = next(iter(batches(dataset, 4, 64, seed=8)))[1]
    assert not np.array_equal(run1[0], other)


def test_targets_shifted_by_one(dataset):
    tokens, targets, mask = sample_batch(dataset, 0, 2, 32, seed=1)
    assert tokens.shape == targets.shape == (2, 32)
    # target[i] is the next token of tokens[i] in the source stream: check
    # via the underlying memmap (offsets are deterministic for the seed).
    rng = np.random.default_rng([1, 0])
    offs = rng.integers(0, len(dataset) - 32, size=2)
    np.testing.assert_array_equal(
        targets[0], np.asarray(dataset.tokens[offs[0] + 1:offs[0] + 33]))
    assert mask.all()


def test_too_short_dataset_rejected(tmp_path):
    ds = TokenDataset.write(str(tmp_path / "s.bin"), np.arange(10))
    with pytest.raises(ValueError, match="tokens"):
        sample_batch(ds, 0, 1, 32)


def test_train_loop_with_resume_on_real_data(dataset, tmp_path):
    """Full story: train 2 steps on dataset batches, checkpoint, resume in
    a fresh state, continue on the SAME schedule — loss trajectory of the
    resumed run matches an uninterrupted run."""
    cfg = llama.llama_tiny()
    mesh = make_mesh(tensor=2, data=4)
    root = str(tmp_path / "ck")

    def run(n_steps, state=None, start=0, step_fn=None, bsh=None, opt=None):
        losses = []
        for step, tok, tgt, m in batches(dataset, 8, 32, start_step=start,
                                         num_steps=n_steps, seed=3,
                                         sharding=bsh):
            state, loss = step_fn(state, tok, tgt, m)
            losses.append(float(loss))
        return state, losses

    with jax.set_mesh(mesh):
        opt = make_optimizer(warmup_steps=1, total_steps=10)
        state, opt = create_train_state(cfg, mesh, jax.random.key(0), opt)
        step_fn, bsh = make_train_step(cfg, mesh, opt)
        state, l01 = run(2, state, 0, step_fn, bsh)
        save_checkpoint(root, state)
        _, l23_cont = run(2, state, 2, step_fn, bsh)

    # "Fresh job": new process state, restore, continue at step 2.
    with jax.set_mesh(mesh):
        fresh, opt2 = create_train_state(cfg, mesh, jax.random.key(5), opt)
        restored = restore_checkpoint(root, fresh)
        step_fn2, bsh2 = make_train_step(cfg, mesh, opt2)
        _, l23_resumed = run(2, restored, 2, step_fn2, bsh2)

    assert l23_resumed == l23_cont


def test_training_cli_end_to_end(dataset, tmp_path):
    """`python -m kukeon_tpu.training.cli`: train, checkpoint, resume —
    black-box over a subprocess (the operator's actual entrypoint)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env["JAX_PLATFORMS"] = "cpu"
    ck = str(tmp_path / "ck")
    base = [sys.executable, "-m", "kukeon_tpu.training.cli",
            "--dataset", dataset.path, "--model", "tiny",
            "--batch", "4", "--seq-len", "32", "--log-every", "2",
            "--ckpt-dir", ck]

    p = subprocess.run(base + ["--steps", "4", "--save-every", "2"],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "step 4 loss" in p.stdout
    assert "checkpoint at step 4" in p.stdout

    p2 = subprocess.run(base + ["--steps", "6"],
                        capture_output=True, text=True, timeout=600, env=env)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "resumed from step 4" in p2.stdout
    assert "step 6 loss" in p2.stdout
