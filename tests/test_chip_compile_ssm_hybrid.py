"""Compile ai21-jamba2-3b-bf16's decode chunk for the chip without the
chip (``tests/test_chip_compile.py`` says what that does and does not show;
its fixtures are used here). A file of its own, like the other families', so
that the test runner's workers share the minutes these compiles take."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from test_chip_compile import (_abstract_cell, _cache_sized_values,  # noqa: F401
                               _for_the_chip, v5e)


@pytest.mark.parametrize("k", [4, 16])
def test_the_state_space_cells_decode_chunk_holds_one_copy_of_each_state_stack(
        v5e, k):
    """jamba2-3b's whole decode program, built by the engine from shapes alone
    through the cell's launcher: 26 mixers' scan state for 64 slots is 545 MB
    of float32 and their convolution tails 51 MB. The chunk donates and
    carries both, a layer's state is written back where it was read, and so
    no instruction makes a second array of either stack's size and the
    temporaries stay under half the scan state; the decode kernel reads the
    two attention layers' rows (20 query heads on one KV head) in place.
    Since PR 41 the scan states are updated by the kernel of
    ``selective_scan.update_held`` in the stack itself (one call in each run
    of mixers): no fusion writes the stack any more."""
    from benchmark import rehearse_compile as rc
    from kukeon_tpu.ops import dispatch

    mesh, eng, args = _abstract_cell(v5e, "ai21-jamba2-3b-bf16")
    before = dispatch.counts().get(("state_update", "pallas"), 0)
    with jax.set_mesh(mesh):
        compiled = eng._decode_chunk.lower(*args, k).compile()
    assert dispatch.counts()[("state_update", "pallas")] == before + 2
    text = compiled.as_text()
    assert "decode_attention" in text and "tpu_custom_call" in text
    assert len(re.findall(r" = \(.*f32\[26,64,16,5120\]\S*\) custom-call\(",
                          text)) == 2 and "ssm_state_update" in text
    assert not re.search(r" = f32\[26,64,16,5120\]\S* fusion\(", text)
    state, rows = args[1].cache.held
    assert state["ssm"].shape == (26, 64, 16, 5120)
    assert state["conv"].shape == (26, 3, 64, 5120)
    assert _cache_sized_values(text, state["ssm"].size, "f32") == []
    # (the weights are bf16 too and larger than these stacks, and the
    # compiler prefetches some of them whole once a chunk: tell the cache's
    # own arrays by their dimensions, a stack's or one layer's)
    def dims(v):
        return sorted(int(n) for n in v[v.index("[") + 1:-1].split(",")
                      if n != "1")

    bf16 = _cache_sized_values(text, state["conv"].size)
    for stack in (state["conv"].shape, rows["k"].shape):
        ours = [sorted(n for n in shape if n != 1)
                for shape in (stack, stack[1:])]
        assert [v for v in bf16 if dims(v) in ours] == []
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < state["ssm"].size * 4 / 2
    assert 6.0e9 < sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        args[0])) < 6.1e9
    assert rc.resident(compiled) / 1e9 == pytest.approx(7.07, rel=0.01)
