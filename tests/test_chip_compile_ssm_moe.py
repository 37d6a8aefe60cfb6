"""Compile granite-4.0-h-small-ep2-bf16's programs for the chip without the
chip (``tests/test_chip_compile.py`` says what that does and does not show).
A file of its own so that the test runner's workers share the minutes these
compiles take: ``test_chip_compile.py`` is the longest file of a whole run.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec
from test_chip_compile import (V5E_HBM_BYTES, _abstract_cell,  # noqa: F401
                               _cache_sized_values, _for_the_chip, v5e)


@pytest.mark.parametrize("program, size, temp_gb", [
    ("decode_chunk", 1, 0.15), ("decode_chunk", 16, 0.15),
    ("prefill", 8192, 1.2)])
def test_the_ssm_moe_cells_programs_copy_no_stack_and_fit_beside_its_cache(
        v5e, program, size, temp_gb):
    """granite-4.0-h-small-ep2-bf16's decode chunk and its largest prefill,
    built by the engine from shapes alone through the cell's launcher: 9.52 GB
    of weights and 2.30 GB of cache (32 slots x nine mixers' 4 MiB of scan
    state, their tails, and one layer's 8192 rows) stay resident. The decode
    chunk holds ONE copy of each state stack (nine calls of the update kernel,
    each aliasing the stack through; a layer's tail written back where it was
    read), reads the attention layer's rows in place, and makes no value of a
    layer's state, of an expert stack or of a mixer's projections (the layers
    are unrolled: a weight is read where it lies, where a slice of a stack by
    a traced index was copied on its way into every ragged product, 0.9 GB a
    mixer and step). The prefill runs the chunked scan's kernel once a mixer
    and fits beside the cache with more than 1 GB to spare. Every expert
    layer's routed products are the kernels of ``ops/expert_products.py`` (a
    decode step's 320 rows, a prefill's blocks of 2048; 4096 and 768 wide):
    no ``ragged-dot`` is left in either program."""
    from benchmark import rehearse_compile as rc
    from kukeon_tpu.ops import dispatch

    mesh, eng, args = _abstract_cell(v5e, "granite-4.0-h-small-ep2-bf16")
    repl = NamedSharding(mesh, PartitionSpec())
    state, rows = args[1].cache.held
    assert state["ssm"].shape == (9, 32, 128, 8192)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (9, 3, 32, 8448)
    assert rows["k"].shape == (1, 32, 8, 8192, 128)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(args[0]))
    cache = sum(x.size * x.dtype.itemsize
                for h in args[1].cache.held for x in h.values())
    assert 9.50e9 < weights < 9.53e9 and 2.29e9 < cache < 2.31e9
    before = dict(dispatch.counts())

    def noted(op):
        return dispatch.counts().get((op, "pallas"), 0) - before.get(
            (op, "pallas"), 0)

    with jax.set_mesh(mesh):
        if program == "decode_chunk":
            compiled = eng._decode_chunk.lower(*args, size).compile()
        else:
            scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=repl)  # noqa: E731
            compiled = eng._prefill.lower(
                args[0], jax.ShapeDtypeStruct((1, size), jnp.int32,
                                              sharding=repl),
                scalar(jnp.int32), args[2], scalar(jnp.float32),
                scalar(jnp.int32), scalar(jnp.float32)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < temp_gb * 1e9
    experts = args[0]["layers"][0]["e_gate"]
    assert experts.shape == (36, 4096, 768)
    assert noted("expert_products") >= 1
    assert "expert_products" in text and "ragged-dot" not in text
    if program == "decode_chunk":
        assert noted("state_update") == 9 and noted("decode_gqa_attention") == 1
        assert "decode_attention" in text and "ssm_state_update" in text
        assert len(re.findall(
            r" = \(.*f32\[9,32,128,8192\]\S*\) custom-call\(", text)) == 9
        assert not re.search(r" = f32\[9,32,128,8192\]\S* fusion\(", text)
        # no second array of the scan states, nor of one mixer's
        assert _cache_sized_values(text, state["ssm"].size // 9, "f32") == []
        # bf16: nothing of an expert stack's size (the K and V stacks and
        # the tails are smaller than one, and the embedding is larger: tell
        # them by their dimensions)
        def dims(v):
            return sorted(int(n) for n in v[v.index("[") + 1:-1].split(",")
                          if n != "1")

        ours = [sorted(n for n in shape if n != 1) for shape in (
            experts.shape, (36, 768, 4096), (4096, 16640), (8192, 4096),
            rows["k"].shape, rows["k"].shape[1:], state["conv"].shape,
            state["conv"].shape[1:])]
        made = _cache_sized_values(text, rows["k"].size // 2)
        assert [v for v in made if dims(v) in ours] == []
        assert rc.resident(compiled) < V5E_HBM_BYTES - (1 << 30)
    else:
        assert noted("ssd_scan") == 9
        assert len(re.findall(r"custom-call\(.*ssd_scan", text)) >= 9 \
            or text.count("ssd_scan") >= 9
        # never a [S, heads, 64, 128] array, nor every chunk's decay matrices
        assert "8192,128,64,128" not in text and "32,128,256,256" not in text
        # beside the cache, which a prefill does not take as an argument
        assert rc.resident(compiled) + cache < V5E_HBM_BYTES - (1 << 30)
