"""models/drawn.py: the seeded recipe's plumbing, which every layered family
brings its table to. The weights ARE the recipe (the benchmark's reference
draws the same values on its own), so a bit that moves here is a fault."""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from kukeon_tpu.models import (drawn, sparse_latent_moe, ssm_hybrid, ssm_moe,
                               window_moe)

PRESETS = {
    "window-moe-tiny": (window_moe, window_moe.window_moe_tiny),
    "ssm-hybrid-tiny": (ssm_hybrid, ssm_hybrid.ssm_hybrid_tiny),
    "sparse-latent-moe-tiny": (sparse_latent_moe,
                               sparse_latent_moe.sparse_latent_moe_tiny),
    "mixed-latent-moe-tiny": (sparse_latent_moe,
                              sparse_latent_moe.mixed_latent_moe_tiny),
    "ssm-moe-tiny": (ssm_moe, ssm_moe.ssm_moe_tiny),
}

# sha256 over each drawn tree's leaves in ``jax.tree.leaves`` order (dtype,
# shape and bytes of every leaf), taken by running ``digest`` below against
# the PARENT of PR 50 (commit 7848716), where each family module still had
# its own ``_leaf_key`` / ``_matrix`` / ``_gain`` / ``init_params``. A PR that
# changes a recipe on purpose takes that preset's digests from its own tree
# and says so; no other PR moves one.
PARENT = {
    ("window-moe-tiny", 0):
        "75143118a7c4aeb365997a699496ffc4b37dae70a3859d432f32bb2619c92659",
    ("window-moe-tiny", 7):
        "99f47ca1b8aed7f90326f8cc409030c00f52d496d2744e25c2ead28f522af53f",
    ("ssm-hybrid-tiny", 0):
        "d996951f62b8fbd2eff4401cd9c1d8a9e207f0c879989fb09e2c64997ddb009f",
    ("ssm-hybrid-tiny", 7):
        "b21f2f867811ebfc598f8f64ecbc481dd8de8856731919c9631f0c120a574318",
    ("sparse-latent-moe-tiny", 0):
        "ddaa1eeab9c292f89d958f46be33c89002d6e844268bac5dc2e70b74a3fd1abc",
    ("sparse-latent-moe-tiny", 7):
        "85b80c1f4e8a6109b1277b617d21fe83daad1a24c67483c6874b6bbd00d29b8f",
    ("mixed-latent-moe-tiny", 0):
        "9528eb34a85ee0476ae4ac1b63c246b6683255c30ac0e73cd8e3c1d87e1c7dee",
    ("mixed-latent-moe-tiny", 7):
        "6220d1e08e381d590234192d5f49c218beb5b234fb96ed4a6fe5a325bae73059",
    ("ssm-moe-tiny", 0):
        "b29bafd25395723c87d9888be5a6ea17533cfe45f0ba0879bc34003ef840db1f",
    ("ssm-moe-tiny", 7):
        "489a556fe5a52c01917954f1f529e53d167a008619ced26dee311e351f0de4c7",
}


def digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("preset,seed", list(PARENT))
def test_a_tiny_presets_drawn_tree_is_the_parents_bit_for_bit(preset, seed):
    model, tiny = PRESETS[preset]
    assert digest(model.init_params(jax.random.key(seed), tiny())) \
        == PARENT[preset, seed]


@pytest.mark.parametrize("layer, expert", [(None, None), (5, None), (5, 3)])
def test_a_leafs_key_is_the_fold_of_its_index_its_layer_and_its_expert(
        layer, expert):
    leaves = ("embed", "norm1", "e_up")
    want = jax.random.fold_in(jax.random.key(7), 2)
    for number in (layer, expert):
        if number is not None:
            want = jax.random.fold_in(want, number)
    got = drawn.leaf_key(leaves, jax.random.key(7), "e_up", layer, expert)
    assert (jax.random.key_data(got) == jax.random.key_data(want)).all()


@pytest.mark.parametrize(
    "preset", [p for p in PRESETS if p != "ssm-hybrid-tiny"])
def test_a_chip_that_holds_the_second_half_draws_those_experts_rows(preset):
    """A chip that holds the second half of the experts draws rows ``count:``
    of what a chip that holds all of them draws, bit for bit, and every leaf
    that is no expert stack the same."""
    model, tiny = PRESETS[preset]
    cfg, key = tiny(), jax.random.key(7)
    half = cfg.num_experts // 2
    whole = model.init_params(key, dataclasses.replace(
        cfg, experts_held=(0, cfg.num_experts)))
    share = model.init_params(key, dataclasses.replace(
        cfg, experts_held=(half, half)))
    stacks = 0
    for (path, all_of), part in zip(
            jax.tree_util.tree_leaves_with_path(whole), jax.tree.leaves(share)):
        name = path[-1].key
        if name in ("e_gate", "e_up", "e_down"):
            stacks += 1
            # [held, in, out], or a period's stack of them [layers, held, ..]
            all_of = np.moveaxis(np.asarray(all_of), -3, 0)[half:]
            part = np.moveaxis(np.asarray(part), -3, 0)
        np.testing.assert_array_equal(np.asarray(part), all_of, err_msg=name)
    assert stacks >= 3


if __name__ == "__main__":      # python tests/test_drawn.py, on the parent
    for (preset, seed) in PARENT:
        model, tiny = PRESETS[preset]
        print(f'    ("{preset}", {seed}):\n        "'
              f'{digest(model.init_params(jax.random.key(seed), tiny()))}",')
