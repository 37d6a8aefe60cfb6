"""Compiled for a DESCRIBED v5e in the CPU sandbox (``tests/test_chip_compile.py``
says how and why; its fixtures are used here, in a file of its own so that the
test workers share the compiles): the window layers' decode kernel at
dots3-note-prev's widths, and the whole decode chunk and the largest prefill
of ``dots3-note-prev-ep8-bf16`` built by the engine from shapes alone through
the cell's launcher."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from test_chip_compile import (  # noqa: F401 — fixtures
    V5E_HBM_BYTES, _abstract_cell, _assert_kernel, _cache_sized_values,
    _for_the_chip, _on, v5e)

CONFIG = "dots3-note-prev-ep8-bf16"


def test_window_decode_attention_compiles_for_v5e(v5e):
    """32 slots x 64 absorbed heads of 1152 lanes (1024 latent values, 64
    rotated, 64 of padding) against rings of 512 rows, read in place in a
    stack of three layers."""
    from kukeon_tpu.ops import dispatch
    from kukeon_tpu.ops import sparse_attention as sa

    d = v5e.devices[0]
    bf, i32 = jnp.bfloat16, jnp.int32
    assert sa.window_kernel_runs(512, sa.WINDOW_TILE, 1152, 1024)
    before = dispatch.counts().get(("window_decode_attention", "pallas"), 0)
    compiled = jax.jit(lambda *a: sa.window_decode_attention(
        *a, window=513, scale=256 ** -0.5, value_dim=1024)).lower(
        _on(d, (32, 64, 1152), bf), _on(d, (32, 1152), bf),
        _on(d, (3, 32, 512, 1152), bf), _on(d, (), i32),
        _on(d, (32,), i32)).compile()
    _assert_kernel(compiled)
    assert dispatch.counts()[("window_decode_attention", "pallas")] > before
    assert "window_latent_decode_attention" in compiled.as_text()
    # the stack is an operand in place: nothing of a ring's size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("program, size, temp_gb", [
    ("decode_chunk", 1, 0.1), ("decode_chunk", 16, 0.1),
    ("prefill", 16384, 1.0)])
def test_the_mixed_latent_cells_programs_fit_beside_its_caches(
        v5e, program, size, temp_gb):
    """8.18 GB of weights and 2.13 GB of cache (32 slots: two full layers of
    20480 rows of 640 + 128 values, three rings of 512 rows of 1152) stay
    resident. A decode chunk runs the index kernel, the selecting attention's
    (20480 rows: 8 runs of 2560 in blocks of 1280) and the window layers'
    kernel once a window layer; it makes no value of a cache layer's size, no
    ``[slots, ring rows, width]`` copy of a ring and no expanded K or V of
    either kind (the absorbed form on both). The prefill runs the selection
    and the masked attention on the full layers and a band on the window
    layers, never ``[S, S]`` scores."""
    from benchmark import rehearse_compile as rc
    from kukeon_tpu.ops import dispatch

    chosen = dispatch.counts().get(("expert_products", "pallas"), 0)
    mesh, eng, args = _abstract_cell(v5e, CONFIG)
    repl = NamedSharding(mesh, PartitionSpec())
    latent, ring = args[1].cache.held
    assert latent["ckv"].shape == (2, 32, 20480, 640)
    assert latent["kidx"].shape == (2, 32, 20480, 128)
    assert ring["wckv"].shape == (3, 32, 512, 1152)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(args[0]))
    cache = sum(x.size * x.dtype.itemsize
                for h in (latent, ring) for x in h.values())
    assert 8.17e9 < weights < 8.20e9 and 2.12e9 < cache < 2.13e9
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
    # the four expert layers' routed products (256 rows a decode step, blocks
    # of 2048 a prefill; 5120 and 1536 wide) are ops/expert_products.py's
    assert dispatch.counts()[("expert_products", "pallas")] > chosen
    assert "expert_products" in text and "ragged-dot" not in text
    assert not re.search(r"bf16\[32,(5120,1536|1536,5120)\]\S* (copy|fusion)\(",
                         text)
    if program == "decode_chunk":
        for kernel in ("sparse_decode_index_scores", "sparse_decode_attention",
                       "window_latent_decode_attention"):
            assert kernel in text, kernel
        # nothing of a full layer's size is made (a ring's layer, 18.9 M
        # values, is smaller than a weight the compiler prefetches into VMEM:
        # the shapes below hold the rings)
        assert _cache_sized_values(text, latent["kidx"].size // 2) == []
        # no copy of the rings or of a slot's selection, no expanded K or V:
        # 32 slots x rows x heads x (192 | 256 | 128) of either kind
        assert not re.search(r"bf16\[32,512,1152\]", text)
        assert not re.search(r"bf16\[(32,2048|20480),640\]", text)
        assert not re.search(
            r"bf16\[32,(512|513|2048|20480),(64|128),(128|192|256)\]", text)
        assert not re.search(
            r"bf16\[32,(64|128),(512|513|2048|20480),(128|192|256)\]", text)
        assert rc.resident(compiled) < V5E_HBM_BYTES
    else:
        assert "sparse_select_rows" in text
        assert "sparse_masked_attention" in text
        assert "window_latent_attention" in text
        assert not re.search(r"f32\[\d+,16384,16384\]", text)
        # beside the cache, which a prefill does not take as an argument
        assert rc.resident(compiled) + cache < V5E_HBM_BYTES
