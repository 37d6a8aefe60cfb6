"""Compile deepseek-v3.2-exp-ep16-bf16's programs for the chip without the
chip (``tests/test_chip_compile.py`` says what that does and does not show;
its fixtures are used here). A file of its own, like the other families', so
that the test runner's workers share the minutes these compiles take."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec
from test_chip_compile import (V5E_HBM_BYTES, _abstract_cell,  # noqa: F401
                               _cache_sized_values, _for_the_chip, v5e)


@pytest.mark.parametrize("program, size, temp_gb", [
    ("decode_chunk", 1, 0.1), ("decode_chunk", 4, 0.1),
    ("decode_chunk", 16, 0.1), ("prefill", 32768, 2.4)])
def test_the_sparse_latent_cells_programs_fit_beside_its_cache(
        v5e, program, size, temp_gb):
    """deepseek-v3.2-exp-ep16-bf16's decode chunk and its largest prefill,
    built by the engine from shapes alone through the cell's launcher: 9.29 GB
    of weights and 4.03 GB of cache (16 slots x 32768 rows x five layers of
    640 + 128 values) stay resident, so a program's temporaries have to fit
    what is left of the chip; a decode chunk of each length the cell warms
    runs the index kernel and the selecting attention's, sorts no scores,
    gathers no copy of the selection (16 slots x 2048 rows x 640) and makes no
    value of a cache layer's size (both kernels read the held stacks in
    place), the prefill runs the selection and the masked attention and never
    a [S, S] array of scores."""
    from benchmark import rehearse_compile as rc
    from kukeon_tpu.ops import dispatch

    chosen = dispatch.counts().get(("expert_products", "pallas"), 0)
    mesh, eng, args = _abstract_cell(v5e, "deepseek-v3.2-exp-ep16-bf16")
    repl = NamedSharding(mesh, PartitionSpec())
    held, = args[1].cache.held
    assert held["ckv"].shape == (5, 16, 32768, 640)
    assert held["kidx"].shape == (5, 16, 32768, 128)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(args[0]))
    cache = sum(x.size * x.dtype.itemsize for x in held.values())
    assert 9.25e9 < weights < 9.30e9 and 4.0e9 < cache < 4.05e9
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
    # the expert layers' routed products (128 rows a decode step, blocks of
    # 2048 a prefill; 7168 and 2048 wide) are ops/expert_products.py's, the
    # held stacks operands where they lie
    assert dispatch.counts()[("expert_products", "pallas")] > chosen
    assert "expert_products" in text and "ragged-dot" not in text
    assert not re.search(r"bf16\[16,(7168,2048|2048,7168)\]\S* (copy|fusion)\(",
                         text)
    if program == "decode_chunk":
        assert "sparse_decode_index_scores" in text
        assert "sparse_decode_attention" in text
        # the only sort left is the sampler's, over the vocabulary
        assert not re.search(r"\[16,3276[89]\]\S* sort\(", text)
        assert "[16,32769]" not in text
        assert not re.search(r"bf16\[(16,2048|32768),640\]", text)
        assert _cache_sized_values(text, held["kidx"].size // 5) == []
        assert rc.resident(compiled) < V5E_HBM_BYTES
    else:
        assert "sparse_select_rows" in text
        assert "sparse_masked_attention" in text
        # beside the cache, which a prefill does not take as an argument
        assert rc.resident(compiled) + cache < V5E_HBM_BYTES
