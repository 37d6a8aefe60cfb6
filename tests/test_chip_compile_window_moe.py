"""Compile trinity-large-preview-ep8-bf16's programs for the chip without the
chip (``tests/test_chip_compile.py`` says what that does and does not show;
its fixtures are used here). A file of its own, like the other families', so
that the test runner's workers share the minutes these compiles take."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec
from test_chip_compile import (  # noqa: F401
    V5E_HBM_BYTES, _abstract_cell, _for_the_chip,
    a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache, programs, v5e)


@pytest.mark.parametrize("config, k, gb", [
    ("trinity-large-preview-ep8-bf16", 4, 12.12),
])
def test_a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache(
        programs, config, k, gb):
    a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache(
        programs, config, k, gb)


def test_the_window_moe_cells_largest_prefill_runs_the_expert_kernels(v5e):
    """trinity-large-preview-ep8-bf16's 8192 bucket through the cell's
    launcher: 32768 (token, choice) pairs in blocks of 2048 under one loop an
    expert layer, each block the two kernels of ``ops/expert_products.py``
    over the held 32 x 3072 x 3072 stacks in place; no ``ragged-dot`` left,
    nothing of a stack's size made, and the program fits beside the cache."""
    from benchmark import rehearse_compile as rc
    from kukeon_tpu.ops import dispatch

    mesh, eng, args = _abstract_cell(v5e, "trinity-large-preview-ep8-bf16")
    repl = NamedSharding(mesh, PartitionSpec())
    chosen = dispatch.counts().get(("expert_products", "pallas"), 0)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=repl)  # noqa: E731
    with jax.set_mesh(mesh):
        compiled = eng._prefill.lower(
            args[0], jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=repl),
            scalar(jnp.int32), args[2], scalar(jnp.float32),
            scalar(jnp.int32), scalar(jnp.float32)).compile()
    assert dispatch.counts()[("expert_products", "pallas")] > chosen
    text = compiled.as_text()
    assert "expert_products" in text and "ragged-dot" not in text
    assert not re.search(r"bf16\[32,3072,3072\]\S* (copy|fusion)\(", text)
    cache = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(args[1].cache))
    assert rc.resident(compiled) + cache < V5E_HBM_BYTES
