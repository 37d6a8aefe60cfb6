"""ops/decode_attention.py against the XLA body of
``ops.attention.decode_gqa_attention``, on the CPU in interpret mode at tiny
widths: the kernel reads the live blocks only, and what it gives for the rows
it was told to read is what the XLA body gives. Whether it compiles for the
chip at the real shapes is ``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import kv_kinds
from kukeon_tpu.ops import attention, decode_attention as da

LAYERS, SLOTS, KV, D, ROWS, BLOCK = 3, 4, 2, 16, 32, 8
TOL = 2e-2     # bf16 outputs of O(1): two ulps


def _inputs(groups: int, dtype=jnp.bfloat16, seed: int = 0):
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (SLOTS, 1, KV, D)
    return (jax.random.normal(ks[0], (SLOTS, 1, KV * groups, D), dtype),
            jax.random.normal(ks[1], shape, dtype),
            jax.random.normal(ks[2], shape, dtype),
            jax.random.normal(ks[3], (LAYERS, SLOTS, ROWS, KV, D), dtype),
            jax.random.normal(ks[4], (LAYERS, SLOTS, ROWS, KV, D), dtype))


def _both(q, kn, vn, ck, cv, count, skip=None, layer=1, **kw):
    """(kernel, XLA body) on one call; the kernel is fed the held layout."""
    count = jnp.asarray(count, jnp.int32)
    skip = None if skip is None else jnp.asarray(skip, jnp.int32)
    ref = attention.decode_gqa_attention(q, kn, vn, ck, cv, jnp.int32(layer),
                                         count, skip=skip)
    out = da.decode_attention(
        q, kn, vn, jnp.swapaxes(ck, 2, 3), jnp.swapaxes(cv, 2, 3), count,
        skip, jnp.int32(layer), rows=BLOCK, interpret=True, **kw)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


# lengths: none, one, one short of a block edge, a block edge, one past it,
# full; four slots a case so that slots of different lengths meet in one walk
@pytest.mark.parametrize("count", [
    [0, 0, 0, 0], [1, 1, 1, 1], [7, 15, 23, 31], [8, 16, 24, 32],
    [9, 17, 25, 1], [32, 32, 32, 32], [0, 32, 0, 7], [5, 0, 0, 0],
    [0, 0, 0, 5],
], ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("groups", [4, 6])
def test_a_full_stack_reads_rows_below_each_slots_count(groups, count):
    out, ref = _both(*_inputs(groups), count)
    assert np.abs(out - ref).max() < TOL


@pytest.mark.parametrize("groups", [4, 6])
def test_the_self_term_alone_gives_v_new(groups):
    """A slot with nothing to read (length 0, or not active) attends to its
    own token: softmax over one score is 1, so the output is ``v_new``
    repeated over the group's query heads, exactly."""
    q, kn, vn, ck, cv = _inputs(groups)
    out, _ = _both(q, kn, vn, ck, cv, [0, 0, 9, 0])
    want = np.asarray(jnp.repeat(vn, groups, axis=2), np.float32)
    assert np.array_equal(out[[0, 1, 3]], want[[0, 1, 3]])
    assert not np.array_equal(out[2], want[2])


def test_an_inactive_slot_reads_nothing_whatever_its_rows_hold():
    """Rows of a slot whose count is 0 may hold anything (a released
    request's): NaN there never reaches an output."""
    q, kn, vn, ck, cv = _inputs(4)
    ck, cv = ck.at[:, 1].set(jnp.nan), cv.at[:, 1].set(jnp.nan)
    out, ref = _both(q, kn, vn, ck, cv, [12, 0, 32, 3])
    assert np.isfinite(out).all()
    assert np.abs(out - ref)[[0, 2, 3]].max() < TOL


def test_rows_past_the_count_inside_the_last_block_are_masked():
    q, kn, vn, ck, cv = _inputs(4)
    big = ck.at[:, :, 11:].set(1e4)     # a score that would take the softmax
    out, _ = _both(q, kn, vn, big, cv, [11, 11, 11, 11])
    want, _ = _both(q, kn, vn, ck, cv, [11, 11, 11, 11])
    assert np.array_equal(out, want)


# a ring of 32 rows at token counts before it wraps, at the wrap, after it
@pytest.mark.parametrize("lengths", [
    [0, 1, 7, 8], [30, 31, 32, 33], [63, 64, 65, 100], [32, 0, 40, 8],
], ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("groups", [4, 6])
def test_a_ring_reads_its_rows_but_the_one_the_new_token_takes(groups,
                                                               lengths):
    kd = kv_kinds.CacheKind("window", (0, 1, 2), ROWS, ring=True)
    count, skip = kv_kinds.valid(kd, jnp.asarray(lengths, jnp.int32))
    out, ref = _both(*_inputs(groups), count, skip)
    assert np.abs(out - ref).max() < TOL


def test_a_wrapped_rings_excluded_row_moves_the_output():
    """After the wrap the excluded row is a row the count covers: the
    kernel leaves it out as the XLA body does, and it matters."""
    q, kn, vn, ck, cv = _inputs(4)
    kd = kv_kinds.CacheKind("window", (0, 1, 2), ROWS, ring=True)
    count, skip = kv_kinds.valid(kd, jnp.full((SLOTS,), 45, jnp.int32))
    assert int(count[0]) == ROWS and int(skip[0]) == 45 % ROWS
    with_skip, ref = _both(q, kn, vn, ck, cv, count, skip)
    without, _ = _both(q, kn, vn, ck, cv, count)
    assert np.abs(with_skip - ref).max() < TOL
    assert np.abs(with_skip - without).max() > TOL


def test_valid_says_what_the_mask_said():
    """``kv_kinds.valid`` as numbers is the [B, rows] mask it used to be."""
    n = jnp.asarray([0, 1, 31, 32, 33, 64, 77], jnp.int32)
    r = np.arange(ROWS)[None, :]
    ring = kv_kinds.CacheKind("window", (0,), ROWS, ring=True)
    count, skip = (np.asarray(x)[:, None] for x in kv_kinds.valid(ring, n))
    was = (r < np.asarray(n)[:, None]) & (r != np.asarray(n)[:, None] % ROWS)
    assert np.array_equal((r < count) & (r != skip), was)
    full = kv_kinds.CacheKind("full", (0,), ROWS)
    count, skip = kv_kinds.valid(full, n)
    assert skip is None and np.array_equal(count, n)


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_blocks_in_flight_across_slot_boundaries(depth):
    """More copies in flight than a slot has blocks: the walk crosses
    slots, skips the empty ones and ends where the blocks end."""
    out, ref = _both(*_inputs(4), [8, 0, 32, 1], depth=depth)
    assert np.abs(out - ref).max() < TOL


@pytest.mark.parametrize("layer", [0, 2])
def test_the_layer_is_an_index_into_the_held_stack(layer):
    out, ref = _both(*_inputs(4), [20, 3, 0, 32], layer=layer)
    assert np.abs(out - ref).max() < TOL


def test_float32_matches_closely():
    out, ref = _both(*_inputs(4, jnp.float32), [7, 16, 0, 32])
    assert np.abs(out - ref).max() < 1e-5


def test_block_rows_follow_the_shapes():
    bf = jnp.bfloat16
    assert da.block_rows(8, 2048, 128, bf) == 512       # Mistral
    assert da.block_rows(8, 8192, 128, bf) == 512       # Trinity, full
    assert da.block_rows(8, 4096, 128, bf) == 512       # Trinity, ring
    assert da.supports(32, 8, 2048, 128, bf)
    assert da.supports(48, 8, 4096, 128, bf)
    assert not da.supports(4, 2, 128, 16, bf)           # the tiny models
    assert not da.supports(32, 8, 2000, 128, bf)        # rows no block tiles


def test_the_cpu_takes_the_xla_body():
    from kukeon_tpu.ops import dispatch

    before = dispatch.counts().get(("decode_gqa_attention", "xla"), 0)
    q, kn, vn, ck, cv = _inputs(4)
    attention.decode_gqa_attention(q, kn, vn, ck, cv, 0,
                                   jnp.asarray([1, 2, 3, 4], jnp.int32))
    assert dispatch.counts()[("decode_gqa_attention", "xla")] == before + 1
