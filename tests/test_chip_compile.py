"""Compile for the chip without the chip.

The TPU compiler is installed here and compiles for a v5e that is described
(`jax.experimental.topologies`), not attached: it refuses what the chip's
compiler would refuse — a kernel tile that does not align, a program that
does not fit 16 GB — and interpret-mode tests on the CPU cannot. These are
the main path's kernels at the real widths and the whole programs of the
flagship cell and of the benchmark's dense configuration; each layered
configuration's whole programs are in a file of its own
(``test_chip_compile_<family>.py``, which use this file's fixtures) so that
the test runner's workers share the minutes. They guard every later PR at no
chip time. A compile that passes is not a chip run: nothing executes, no
number here is a device metric.

The dispatchers ask `jax.default_backend()` and here that says "cpu"; the
tests steer it themselves (monkeypatch), not through an option of the
program.
"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else the compiler logs under /tmp
# libtpu takes a one-process lock (/tmp/libtpu_lockfile) even to describe a
# topology; compiling for a described chip opens no device, so sharing the
# library with another such process is safe — without this a concurrent
# rehearsal (or test worker) turns every test here into a skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402

from kukeon_tpu.models import llama  # noqa: E402
from kukeon_tpu.ops import decode_attention as da  # noqa: E402
from kukeon_tpu.ops import flash_attention as fa  # noqa: E402
from kukeon_tpu.ops import selective_scan as ss  # noqa: E402
from kukeon_tpu.ops import ssd_scan as sd  # noqa: E402

# bytes_limit the attached v5e reports (memory_stats on the chip, PR 22).
V5E_HBM_BYTES = 16909336064


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _for_the_chip(monkeypatch):
    """Dispatch as on the chip, and keep the persistent compile cache out
    of it: an executable compiled for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel is not in the compiled program (XLA path taken)"


@pytest.mark.parametrize("s", [1024, 8192])
def test_flash_attention_compiles_for_v5e(v5e, s):
    d = v5e.devices[0]
    qkv = _on(d, (1, s, 32, 128), jnp.bfloat16)
    pos = _on(d, (1, s), jnp.int32)
    compiled = jax.jit(fa.flash_attention).lower(
        qkv, qkv, qkv, pos, pos).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k", [4, 16])
def test_decode_chunk_at_8b_int8_fits_one_v5e(v5e, k):
    """The flagship cell's whole decode program — llama3-8b int8, 32 layers,
    4 slots x 4096 rows of bf16 KV, 4 or 16 steps per chunk — built by the
    engine itself from shapes alone, compiled for one described v5e: the
    bytes it keeps resident (arguments + outputs - donated aliases +
    temporaries + code) fit the chip's HBM. This is the program that holds
    the most at once (weights + whole cache), and its temporaries stay under
    half of the K + V cache: the state holds the cache in the scan carry's
    own layout (engine._kv_major), so no chunk transposes it in and out;
    a state held row-major costs a whole cache and more of temporaries."""
    from kukeon_tpu.parallel import make_mesh
    from kukeon_tpu.serving import ServingEngine

    class AbstractOnly:
        """A checkpoint stream that only knows its shapes: the engine
        builds shardings and abstract params from it and touches no
        device (async_load's thread finds nothing to upload)."""

        def __init__(self, tree):
            self.abstract_params = tree

        def __iter__(self):
            return iter(())

        def stat_snapshot(self):
            return {}

    cfg = llama.llama3_8b()
    mesh = make_mesh(tensor=1, devices=v5e.devices[:1])
    abstract = jax.eval_shape(
        lambda k: llama.init_quantized_params(k, cfg), jax.random.key(0))
    eng = ServingEngine(cfg, AbstractOnly(abstract), mesh, num_slots=4,
                        max_seq_len=4096, async_load=True, kv_page_tokens=0)
    repl = NamedSharding(mesh, PartitionSpec())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    key = jax.eval_shape(lambda: jax.random.key(0))
    state = eng._abstract_state()
    with jax.set_mesh(mesh):
        compiled = eng._decode_chunk.lower(
            eng._abstract_params, state,
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl),
            sds((4,), jnp.float32), sds((4,), jnp.int32),
            sds((4,), jnp.float32), k).compile()
    m = compiled.memory_analysis()
    resident = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes
                + m.generated_code_size_in_bytes)
    # int8 weights alone are ~8 GB; the program must hold them AND fit.
    assert 8e9 < m.argument_size_in_bytes < V5E_HBM_BYTES
    assert resident < V5E_HBM_BYTES, (
        f"decode_chunk keeps {resident / 1e9:.2f} GB resident; the chip has "
        f"{V5E_HBM_BYTES / 1e9:.2f} GB")
    kv_bytes = sum(x.size * x.dtype.itemsize
                   for x in (state.cache.k, state.cache.v))
    assert m.temp_size_in_bytes < kv_bytes / 2, (
        f"decode_chunk (k={k}) holds {m.temp_size_in_bytes / 1e9:.2f} GB of "
        f"temporaries beside a K + V cache of {kv_bytes / 1e9:.2f} GB: a "
        "whole-cache copy is back in the program")


# Decode attention at the benchmark's shapes: the held stack [layers, B, KV,
# rows, D], query heads a KV head, whether a row is excluded (a ring).
@pytest.mark.parametrize("layers, slots, rows, groups, ring, kv", [
    (32, 8, 2048, 4, False, 8),     # mistral-7b-v0.3: 8 slots x 2048 rows
    (1, 32, 8192, 6, False, 8),     # trinity share: the full layer
    (4, 32, 4096, 6, True, 8),      # trinity share: the four rings
    (2, 64, 4096, 20, False, 1),    # jamba2-3b: 20 query heads on ONE KV head
])
def test_decode_attention_compiles_for_v5e(v5e, layers, slots, rows, groups,
                                           ring, kv):
    d = v5e.devices[0]
    bf, i32 = jnp.bfloat16, jnp.int32
    new = _on(d, (slots, 1, kv, 128), bf)
    held = _on(d, (layers, slots, kv, rows, 128), bf)
    compiled = jax.jit(
        lambda q, kn, vn, k, v, count, skip, layer: da.decode_attention(
            q, kn, vn, k, v, count, skip if ring else None, layer)
    ).lower(_on(d, (slots, 1, kv * groups, 128), bf), new, new, held, held,
            _on(d, (slots,), i32), _on(d, (slots,), i32),
            _on(d, (), i32)).compile()
    _assert_kernel(compiled)
    # the stacks are operands in place: nothing of a layer's size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# The selective scan at jamba2-3b's widths (5120 channels, 16 states): the
# smallest and the largest prompt bucket of its cell.
@pytest.mark.parametrize("steps", [64, 2048])
def test_selective_scan_compiles_for_v5e(v5e, steps):
    d = v5e.devices[0]
    f32 = jnp.float32
    assert ss.kernel_chunk(steps, 5120, 1) == min(steps, ss.CHUNK)
    by_time, by_state = _on(d, (steps, 5120), f32), _on(d, (steps, 16), f32)
    compiled = ss.scan_kernel.lower(
        by_time, by_time, by_time, by_state, by_state, _on(d, (16, 5120), f32),
        _on(d, (5120,), f32), chunk=ss.kernel_chunk(steps, 5120, 1)).compile()
    _assert_kernel(compiled)
    assert "selective_scan" in compiled.as_text()
    # HBM never sees an array of [steps, channels, states]
    assert (compiled.memory_analysis().temp_size_in_bytes
            < steps * 5120 * 16 * 4 / 4)


# A decode step's update of the held scan states at jamba2-3b's sizes: 26
# mixers x 64 slots x [16, 5120] float32, the stack an operand in place.
def test_state_update_kernel_compiles_for_v5e(v5e):
    d = v5e.devices[0]
    f32, i32 = jnp.float32, jnp.int32
    assert ss.update_kernel_runs(5120, 16, 1)
    assert not ss.update_kernel_runs(5120, 16, 4)   # GSPMD partitions no kernel
    assert not ss.update_kernel_runs(128, 8, 1)     # the tiny preset's channels
    by_slot, by_state = _on(d, (64, 5120), f32), _on(d, (64, 16), f32)
    compiled = jax.jit(ss.update_kernel, donate_argnums=0).lower(
        _on(d, (26, 64, 16, 5120), f32), _on(d, (), i32), _on(d, (64,), i32),
        _on(d, (1,), i32), by_slot, by_slot, by_slot, by_state, by_state,
        _on(d, (16, 5120), f32), _on(d, (5120,), f32)).compile()
    _assert_kernel(compiled)
    assert "ssm_state_update" in compiled.as_text()
    # the stack goes in and comes out as ONE array: donated, aliased through
    # the call, and nothing of its size (or of one mixer's) is made beside it
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 26 * 64 * 16 * 5120 * 4
    assert m.temp_size_in_bytes < 64 * 16 * 5120 * 4 / 4


def _abstract_cell(v5e, config_name):
    """The engine of a benchmark configuration over shapes alone, as
    ``benchmark/rehearse_compile.py`` builds it, and its decode chunk's
    arguments but the chunk's length."""
    import json

    from benchmark import plugins, rehearse_compile as rc
    from kukeon_tpu.parallel import make_mesh

    with open(os.path.join(plugins.HERE, "configs", config_name + ".json")) as f:
        config = json.load(f)
    mesh = make_mesh(tensor=1, devices=v5e.devices[:1])
    _cfg, eng = rc.abstract_engine(config, mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    B = config["serving"]["num_slots"]
    key = jax.eval_shape(lambda: jax.random.key(0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    return mesh, eng, (eng._abstract_params, eng._abstract_state(),
                       sds(key.shape, key.dtype), sds((B,), jnp.float32),
                       sds((B,), jnp.int32), sds((B,), jnp.float32))


def _cache_sized_values(text: str, cache_elements: int,
                        dtype: str = "bf16") -> list[str]:
    """Instructions of a compiled program that MAKE a value of a layer of
    the cache's size or more, in the cache's dtype, in HBM or in VMEM
    (``S(1)``): a copy, a transpose or a fusion that materializes a slice of
    it. In-place updates (a dynamic-update-slice, or a fusion whose root is
    one), operands passed through and the loop's own plumbing make nothing,
    and neither does an instruction INSIDE a fusion's ``calls=`` computation:
    a slice fused into a product is read where it lies and never written."""
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.-]+)", text))
    roots, found, inside = {}, [], None   # computation -> its root's operation
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) \(", line)
        if head:
            inside = head.group(1)
        root = re.match(r"\s*ROOT \S+ = \S+ ([\w-]+)\(", line)
        if root and inside:
            roots[inside] = root.group(1)
        m = re.match(r"\s*(?:ROOT )?(\S+) = " + dtype
                     + r"\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m and inside not in fused and m.group(3) not in (
                "get-tuple-element", "parameter", "bitcast", "while", "tuple",
                "dynamic-update-slice", "custom-call"):
            found.append((m, re.search(r"calls=(%[\w.-]+)", line)))
    made = []
    for m, calls in found:
        if (m.group(3) == "fusion" and calls
                and roots.get(calls.group(1)) == "dynamic-update-slice"):
            continue
        n = 1
        for dim in m.group(2).split(","):
            n *= int(dim)
        if n >= cache_elements:
            made.append(f"{m.group(3)} {m.group(1)} [{m.group(2)}]")
    return made


def _lower_program(mesh, eng, args, kind: str, sizes: tuple):
    """One of a cell's programs with the arguments
    ``benchmark/rehearse_compile.py`` states for it: a decode chunk of
    ``sizes[0]`` steps, a prefill of ``sizes[0]`` tokens, or a dense cell's
    ``prefill_ext`` of ``sizes[1]`` tokens behind ``sizes[0]`` cached rows."""
    cfg, repl = eng.cfg, NamedSharding(mesh, PartitionSpec())

    def sds(shape, dtype, sh=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def kv(rows):
        return sds((cfg.num_layers, 1, rows, cfg.num_kv_heads, cfg.head_dim),
                   cfg.dtype, eng._cache_shardings()[0])

    params, _state, key = args[:3]
    f32, i32 = sds((), jnp.float32), sds((), jnp.int32)
    if kind == "decode_chunk":
        return eng._decode_chunk.lower(*args, sizes[0])
    tokens = sds((1, sizes[-1]), jnp.int32)
    if kind == "prefill":
        return eng._prefill.lower(params, tokens, i32, key, f32, i32, f32)
    return eng._prefill_ext.lower(params, kv(sizes[0]), kv(sizes[0]), i32,
                                  tokens, i32, key, f32, i32, f32)


@pytest.fixture(scope="module")
def programs(v5e):
    """``programs(config, kind, *sizes)`` -> (eng, args, compiled, noted): a
    benchmark configuration's engine over shapes alone and one of its
    programs compiled for the described chip, each built ONCE a module
    however many tests read it (a whole program is 25-65 s of compiling);
    ``noted`` is what the dispatchers counted while the program was traced."""
    from kukeon_tpu.ops import dispatch

    cells, built = {}, {}

    def get(config, kind, *sizes):
        if config not in cells:
            cells[config] = _abstract_cell(v5e, config)
        mesh, eng, args = cells[config]
        if (config, kind, sizes) not in built:
            before = dispatch.counts()
            with jax.set_mesh(mesh):
                compiled = _lower_program(mesh, eng, args, kind,
                                          sizes).compile()
            built[config, kind, sizes] = compiled, {
                op: n - before.get(op, 0)
                for op, n in dispatch.counts().items()}
        return (eng, args, *built[config, kind, sizes])

    return get


def a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache(
        programs, config, k, gb):
    """A benchmark cell's whole decode program, built by the engine from
    shapes alone through the cell's launcher: the decode kernel is in it
    (one call a layer kind the scan holds), nothing in it makes a value the
    size of one layer's K or V of one kind (a Pallas operand takes its
    default layout, so a kernel fed a slice or a transposed view of the
    stack would bring a copy of it back), and what it keeps resident is what
    it kept with the XLA body."""
    from benchmark import rehearse_compile as rc

    _eng, args, compiled, noted = programs(config, "decode_chunk", k)
    assert noted[("decode_gqa_attention", "pallas")] > 0
    text = compiled.as_text()
    # a cell with an expert layer runs its routed products as the kernels of
    # ops/expert_products.py (Trinity: 128 rows a step, 3072 x 3072 a held
    # expert); a dense cell has neither them nor a ragged product
    routed = "e_gate" in str(jax.tree_util.tree_structure(args[0]))
    assert (noted.get(("expert_products", "pallas"), 0) > 0) is routed
    assert ("expert_products" in text) is routed and "ragged-dot" not in text
    assert "decode_attention" in text and "tpu_custom_call" in text
    held = args[1].cache.k           # one stack, or one a kind
    smallest = min(x.size // x.shape[0]
                   for x in (held if isinstance(held, tuple) else (held,)))
    assert _cache_sized_values(text, smallest) == []
    assert rc.resident(compiled) / 1e9 == pytest.approx(gb, rel=0.01)


# (the window cell's case is in tests/test_chip_compile_window_moe.py)
@pytest.mark.parametrize("config, k, gb", [
    ("mistral-7b-v0.3-int8", 16, 9.41),
    ("mistral-7b-v0.3-int8", 4, 9.41),
])
def test_a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache(
        programs, config, k, gb):
    a_cells_decode_chunk_runs_the_kernel_and_copies_no_cache(
        programs, config, k, gb)


@pytest.mark.parametrize("kind, sizes", [
    ("decode_chunk", (4,)), ("decode_chunk", (16,)),
    ("prefill", (256,)), ("prefill", (2048,)),
    ("prefill_ext", (1024, 256)),   # a turn of agent-sessions.json's warm-up
], ids=lambda v: v if isinstance(v, str) else "+".join(map(str, v)))
def test_a_dense_cells_programs_make_no_value_of_a_weights_size(
        programs, kind, sizes):
    """Every quantized product of `mistral-7b-v0.3-int8` takes its stack and
    the layer's index and reads one layer in place: no instruction of a
    decode chunk or a prefill MAKES an int8 value of one layer of `wk`
    (4096 x 1024 elements) or more, and a chunk's temporaries stay small.
    `llama._qkv` ends its three products at their flat results for this:
    with the rotation fused into q's and k's products the compiler wants
    those weights transposed, and a chunk re-lays both stacks once a call
    (`copy.105 s8[32,4096,4096]`, `copy.104 s8[32,4096,1024]`: 0.677 GB of
    temporaries) and copies a layer of each to VMEM every layer of every
    step (`constant_dynamic-slice_fusion.7 / .6`), and a prefill slices and
    transposes a layer of each. The one int8 value a long prefill does make
    is the prompt's own rows of the embedding table, `s8[tokens, hidden]`:
    a lookup's result, not a weight."""
    eng, args, compiled, _noted = programs(
        "mistral-7b-v0.3-int8", kind, *sizes)
    wk = args[0]["layers"]["wk"]["q"]
    assert wk.dtype == jnp.int8 and wk.shape[1:] == (4096, 1024)
    made = _cache_sized_values(compiled.as_text(), wk.size // wk.shape[0], "s8")
    embedded = f"[{sizes[-1]},{eng.cfg.hidden_size}]"
    assert [v for v in made if not v.endswith(embedded)] == []
    if kind == "decode_chunk":
        assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


# The selecting attention's kernels (ops/sparse_attention.py) at the
# deepseek-v3.2-exp share's widths: 64 index heads of 128, a chunk of 4096
# queries against the smallest and the largest prompt bucket, a group of 16
# heads of 192 / 128 under one mask, and a decode step's 16 queries against
# the held stack of index keys, then its 128 absorbed heads against the held
# stack of latent rows.
@pytest.mark.parametrize("keys", [4096, 32768])
def test_sparse_prefill_kernels_compile_for_v5e(v5e, keys):
    from kukeon_tpu.ops import sparse_attention as sa

    d = v5e.devices[0]
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    Q = 4096
    assert sa.kernels_run(Q, keys, 128)
    select = jax.jit(lambda q, w, k, r: sa.select_rows(
        q, w, k, r, topk=2048)).lower(
        _on(d, (Q, 64, 128), bf), _on(d, (Q, 64), f32),
        _on(d, (keys, 128), bf), _on(d, (), i32)).compile()
    _assert_kernel(select)
    assert "sparse_select_rows" in select.as_text()
    # the scores never reach HBM: what is made is the int8 mask's size
    assert select.memory_analysis().temp_size_in_bytes < Q * keys
    attend = jax.jit(lambda q, k, v, m, r: sa.masked_attention(
        q, k, v, m, r, scale=0.1)).lower(
        _on(d, (16, Q, 192), bf), _on(d, (16, keys, 192), bf),
        _on(d, (16, keys, 128), bf), _on(d, (keys // 512, Q, 512), jnp.int8),
        _on(d, (), i32)).compile()
    _assert_kernel(attend)
    assert "sparse_masked_attention" in attend.as_text()


def test_sparse_decode_index_scores_compile_for_v5e(v5e):
    from kukeon_tpu.ops import sparse_attention as sa

    d = v5e.devices[0]
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    compiled = jax.jit(sa.decode_index_scores).lower(
        _on(d, (16, 64, 128), bf), _on(d, (16, 64), f32),
        _on(d, (5, 16, 32768, 128), bf), _on(d, (), i32),
        _on(d, (16,), i32)).compile()
    _assert_kernel(compiled)
    # the stack is an operand in place: nothing of a layer's size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_sparse_decode_attention_compiles_for_v5e(v5e):
    from kukeon_tpu.ops import sparse_attention as sa

    d = v5e.devices[0]
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    from kukeon_tpu.ops import dispatch

    assert sa.decode_kernel_runs(32768, 640)
    before = dispatch.counts().get(("decode_attention", "pallas"), 0)
    compiled = jax.jit(lambda *a: sa.decode_attention(
        *a, topk=2048, scale=0.1, value_dim=512)).lower(
        _on(d, (16, 128, 640), bf), _on(d, (16, 640), bf), _on(d, (16,), f32),
        _on(d, (16, 32768), f32), _on(d, (5, 16, 32768, 640), bf),
        _on(d, (), i32), _on(d, (16,), i32)).compile()
    _assert_kernel(compiled)
    assert dispatch.counts()[("decode_attention", "pallas")] > before
    text = compiled.as_text()
    assert "sparse_decode_attention" in text and " sort(" not in text
    # the stack is an operand in place; what is made beside it is the scores
    # in the floats' order (2 MB), never a gathered copy of the selection
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# The chunked state-space scan at granite-4.0-h-small's widths (128 heads of
# 64 channels, 128 states, chunks of 256): the smallest and the largest prompt
# bucket of its cell, and the one bucket of the engine's default ones that is
# a single chunk of 128 (64 rows are no lane tile: the XLA body's).
@pytest.mark.parametrize("steps", [128, 512, 8192])
def test_ssd_scan_compiles_for_v5e(v5e, steps):
    d = v5e.devices[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    chunk = min(256, steps)
    assert sd.kernel_runs(steps, 128, 64, 128, chunk, 1)
    assert not sd.kernel_runs(steps, 128, 64, 128, chunk, 4)    # GSPMD
    assert not sd.kernel_runs(64, 128, 64, 128, 64, 1)      # no lane tile
    assert not sd.kernel_runs(32, 4, 8, 16, 8, 1)           # the tiny preset
    by_state = _on(d, (steps, 128), bf16)
    compiled = sd.scan_kernel.lower(
        _on(d, (steps, 8192), bf16), _on(d, (steps, 128), f32), by_state,
        by_state, _on(d, (128,), f32), _on(d, (128,), f32), heads=128,
        chunk=chunk).compile()
    _assert_kernel(compiled)
    assert "ssd_scan" in compiled.as_text()
    # HBM never sees an array of [steps, heads, 64, 128] (34 GB in float32 at
    # 8192 steps), nor a chunk's [heads, 256, 256] decay matrices (33.5 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# A decode step's update of the held scan states at granite-4.0-h-small's
# sizes: 9 mixers x 32 slots x [128, 8192] float32 (4 MiB a slot and mixer),
# the decay ONE row (a head's scalar over its channels), the stack in place.
def test_state_update_kernel_compiles_with_a_decay_of_one_row_for_v5e(v5e):
    d = v5e.devices[0]
    f32, i32 = jnp.float32, jnp.int32
    assert ss.update_kernel_runs(8192, 128, 1)
    by_slot, by_state = _on(d, (32, 8192), f32), _on(d, (32, 128), f32)
    compiled = jax.jit(ss.update_kernel, donate_argnums=0).lower(
        _on(d, (9, 32, 128, 8192), f32), _on(d, (), i32), _on(d, (32,), i32),
        _on(d, (1,), i32), by_slot, by_slot, by_slot, by_state, by_state,
        _on(d, (1, 8192), f32), _on(d, (8192,), f32)).compile()
    _assert_kernel(compiled)
    assert "ssm_state_update" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 9 * 32 * (4 << 20)
    assert m.temp_size_in_bytes < 32 * (4 << 20) / 4


# An expert layer's routed products at the four cells' widths (held experts,
# H, I, a decode step's slots x top-k rows): the decode step's one block and
# a prefill's block of 2048 rows, both forms. A width that does not tile, a
# window that does not align or chunks that do not fit VMEM fail here and not
# at a cell's boot.
@pytest.mark.parametrize("rows", ["decode", 2048])
@pytest.mark.parametrize("count, H, I, decode_rows", [
    (36, 4096, 768, 320),       # granite-4.0-h-small-ep2-bf16
    (32, 5120, 1536, 256),      # dots3-note-prev-ep8-bf16
    (32, 3072, 3072, 128),      # trinity-large-preview-ep8-bf16
    (16, 7168, 2048, 128),      # deepseek-v3.2-exp-ep16-bf16
])
def test_expert_products_compile_for_v5e(v5e, count, H, I, decode_rows, rows):
    from kukeon_tpu.models import expert_layer as el
    from kukeon_tpu.ops import expert_products as ep

    d = v5e.devices[0]
    rows = decode_rows if rows == "decode" else rows
    bf16 = jnp.bfloat16
    assert el.kernel_runs(rows, H, I, bf16, 1)
    assert not el.kernel_runs(rows, H, I, bf16, 4)  # GSPMD partitions no kernel
    offsets = _on(d, (count + 1,), jnp.int32)
    for fn, operands in (
            (ep.gate_up, (_on(d, (rows, H), bf16), _on(d, (count, H, I), bf16),
                          _on(d, (count, H, I), bf16))),
            (ep.down, (_on(d, (rows, I), bf16), _on(d, (count, I, H), bf16)))):
        compiled = jax.jit(fn).lower(*operands, offsets).compile()
        _assert_kernel(compiled)
        text = compiled.as_text()
        assert "expert_products" in text
        # the stacks are operands where they lie: nothing of one's size is
        # made, and beside the rows in and out nothing is allocated
        assert _cache_sized_values(text, count * H * I) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
