"""The seeded recipe's plumbing: how a leaf's key is folded and how the common
kinds of leaf are drawn.

A layered family's checkpoint-less weights ARE their recipe: a leaf is a
seeded draw under a key folded from (seed, the leaf's place in the family's
``LEAVES``, the layer's number in the model, the expert's number among all the
router's scores), so a chip that holds experts 32-63 draws exactly those, and
the family's reference under ``benchmark/reference/`` draws the same values
without importing the program (tests/bench pins the two; ``correct`` on the
chip hangs on it). A family module brings its TABLE (``LEAVES``, a layer's
name -> (kind, shape, fan-in)), the kinds that are its own and the shape of
its tree; the fold order and the draws are here and nowhere else
(tests/test_drawn.py pins every tiny preset's tree bit for bit).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

GAIN_STD = 0.1


def leaf_key(leaves: tuple, key, name: str, layer=None, expert=None):
    key = jax.random.fold_in(key, leaves.index(name))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    return key


def matrix(key, shape, fan_in, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5 * scale)).astype(dtype)


def gain(key, shape, dtype):
    return (1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def draw(leaves: tuple, key, cfg, name, kind, shape, fan_in, layer):
    """A leaf of one of the kinds every family shares (``gain``, ``router`` in
    float32, ``experts``, anything else a plain matrix): what a family's own
    ``_draw`` falls through to after the kinds that are its own."""
    if kind == "experts":
        # the held stack [count, *shape], a key an expert: the float32
        # transient of a stack is ONE expert's matrix
        first, count = cfg.experts_held
        return jax.lax.map(
            lambda e: matrix(leaf_key(leaves, key, name, layer, e), shape,
                             fan_in, cfg.dtype), first + jnp.arange(count))
    k = leaf_key(leaves, key, name, layer)
    if kind == "gain":
        return gain(k, shape, cfg.dtype)
    return matrix(k, shape, fan_in,
                  jnp.float32 if kind == "router" else cfg.dtype)


def init(draw_params: Callable, key: jax.Array, cfg, shardings: Any = None):
    """Checkpoint-less init on the device(s) in ONE jitted program that takes
    the key as its argument, every leaf born in its serving sharding
    (``shardings``: the tree ``parallel.sharding.param_shardings`` gives for
    this function's ``jax.eval_shape``). One program whatever the seed, so
    the persistent compile cache finds it again at the next boot (a program
    a leaf, each under the cache's one-second floor and with the key baked
    in, compiled anew at every boot: 75-80 s of a 126 s set-up, my chip
    runs, PR 30)."""
    return jax.jit(lambda k: draw_params(k, cfg),
                   out_shardings=shardings)(key)


def whole(params):
    """Everything whole on the one chip."""
    from jax.sharding import PartitionSpec

    return jax.tree.map(lambda _: PartitionSpec(), params)
