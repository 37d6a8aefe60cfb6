"""ops/expert_products.py against a dense reference, an expert at a time.

The kernels run under ``interpret=True`` on the CPU at scaled-down widths of
the four expert-layer cells' shapes, with chunks small enough that an
expert's matrix comes in several and the walk crosses expert boundaries with
copies in flight. The reference takes each expert's rows through plain
float32 products and rounds where the kernels round.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.ops import expert_products as ep

# (held experts, H, I, a decode step's rows = slots x top-k), each cell's
# published ratio of H to I kept: granite-4.0-h-small 36 x 4096 x 768,
# dots3 32 x 5120 x 1536, Trinity 32 x 3072 x 3072, DeepSeek 16 x 7168 x 2048
CELLS = {
    "granite": (9, 640, 128, 8 * 10),
    "dots3": (8, 640, 256, 8 * 8),
    "trinity": (8, 384, 384, 16 * 4),
    "deepseek": (4, 896, 256, 4 * 8),
}
PREFILL_ROWS = 320      # past two windows of 128, not a whole number of them
CHUNK = 640 * 128 * 2   # one lane tile of the widest K here: chunks of [K, 128]


def _sizes(case: str, count: int, rows: int) -> list[int]:
    rng = np.random.default_rng(len(case) + count + rows)
    sizes = [0] * count
    if case == "every_group_empty":
        pass
    elif case == "one_group_holds_every_row":
        sizes[count // 2] = rows
    elif case == "groups_straddle_windows":
        # rows in every group, the boundaries wherever they fall, and one
        # group longer than a window where the call is
        sizes = (rng.multinomial(rows - count, np.ones(count) / count) + 1)
        sizes = sizes.tolist()
    elif case == "a_group_of_one_row":
        sizes[0], sizes[count - 1] = 1, 1
        sizes[1] = 37 if rows > 40 else 3
    elif case == "rows_past_the_last_group":
        sizes[1], sizes[2] = 5, rows // 4
    elif case == "few_reached_of_many":
        for e in rng.choice(count, 3, replace=False):
            sizes[e] = int(rng.integers(1, 9))
    else:
        raise AssertionError(case)
    return sizes


CASES = ["every_group_empty", "one_group_holds_every_row",
         "groups_straddle_windows", "a_group_of_one_row",
         "rows_past_the_last_group", "few_reached_of_many"]


def _operands(cell: str, rows: int, dtype):
    count, H, I, _ = CELLS[cell]
    ks = jax.random.split(jax.random.key(count * H + rows), 4)

    def draw(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale
                ).astype(dtype)

    return (draw(ks[0], (rows, H), 1.0), draw(ks[1], (count, H, I), H ** -.5),
            draw(ks[2], (count, H, I), H ** -.5),
            draw(ks[3], (count, I, H), I ** -.5))


def _dense(x, w_gate, w_up, w_down, offsets):
    """(silu(x Wg) * (x Wu), that through Wd) of each expert's rows in
    float32, rounded to x's dtype where the served chain rounds; zeros
    where no group is."""
    f32 = np.float32

    def rounded(a):
        return np.asarray(jnp.asarray(a, f32).astype(x.dtype).astype(f32))

    x32 = np.asarray(x, f32)
    act = np.zeros((x.shape[0], w_gate.shape[2]), f32)
    y = np.zeros((x.shape[0], w_down.shape[2]), f32)
    for e in range(w_gate.shape[0]):
        at = slice(int(offsets[e]), int(offsets[e + 1]))
        gate = rounded(x32[at] @ np.asarray(w_gate[e], f32))
        up = rounded(x32[at] @ np.asarray(w_up[e], f32))
        act[at] = rounded(rounded(gate / (1 + np.exp(-gate))) * up)
        y[at] = rounded(act[at] @ np.asarray(w_down[e], f32))
    return act, y


def _check(cell: str, rows: int, sizes, dtype=jnp.bfloat16, **how):
    x, w_gate, w_up, w_down = _operands(cell, rows, dtype)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    held = int(offsets[-1])
    assert held <= rows
    how = dict(interpret=True, chunk_bytes=CHUNK, **how)
    act = ep.gate_up(x, w_gate, w_up, jnp.asarray(offsets), **how)
    y = ep.down(act, w_down, jnp.asarray(offsets), **how)
    assert act.shape == (rows, w_gate.shape[2]) and act.dtype == x.dtype
    assert y.shape == x.shape and y.dtype == x.dtype
    want_act, want_y = _dense(x, w_gate, w_up, w_down, offsets)
    # one step of the output's dtype at the output's size
    step = 2.0 ** (-7 if dtype == jnp.bfloat16 else -16)
    for got, want in ((act, want_act), (y, want_y)):
        got = np.asarray(got.astype(jnp.float32))[:held]
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(
            got, want[:held], rtol=0,
            atol=step * max(1.0, float(np.abs(want).max())))
    return x, (w_gate, w_up, w_down), offsets, y


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_decode_steps_rows_against_the_dense_reference(cell, case):
    """``rows == slots x top-k``, one window or less."""
    count, _, _, rows = CELLS[cell]
    _check(cell, rows, _sizes(case, count, rows))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_prefill_blocks_rows_against_the_dense_reference(cell, case):
    count = CELLS[cell][0]
    _check(cell, PREFILL_ROWS, _sizes(case, count, PREFILL_ROWS))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_what_lies_past_the_last_group_is_never_read_back(cell):
    """NaN in every row past the last group, of the rows and of what the
    first kernel left there, moves no row of a group."""
    count = CELLS[cell][0]
    rows = PREFILL_ROWS
    sizes = _sizes("rows_past_the_last_group", count, rows)
    x, stacks, offsets, y = _check(cell, rows, sizes)
    held = int(offsets[-1])
    poisoned = x.at[held:].set(jnp.nan)
    how = dict(interpret=True, chunk_bytes=CHUNK)
    act = ep.gate_up(poisoned, stacks[0], stacks[1], jnp.asarray(offsets),
                     **how)
    again = ep.down(act.at[held:].set(jnp.nan), stacks[2],
                    jnp.asarray(offsets), **how)
    np.testing.assert_array_equal(np.asarray(again[:held], np.float32),
                                  np.asarray(y[:held], np.float32))


@pytest.mark.parametrize("how", [
    dict(depth=2), dict(depth=4), dict(window=64),
    dict(chunk_bytes=1 << 30),      # an expert's matrix in ONE chunk
], ids=lambda how: "-".join(f"{k}{v}" for k, v in how.items()))
def test_the_walk_is_the_same_product_at_any_depth_chunk_and_window(how):
    count = CELLS["dots3"][0]
    how = dict(dict(chunk_bytes=CHUNK), **how)
    x, w_gate, w_up, w_down = _operands("dots3", PREFILL_ROWS, jnp.bfloat16)
    sizes = _sizes("groups_straddle_windows", count, PREFILL_ROWS)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    want = ep.down(ep.gate_up(x, w_gate, w_up, offsets, interpret=True,
                              chunk_bytes=CHUNK),
                   w_down, offsets, interpret=True, chunk_bytes=CHUNK)
    got = ep.down(ep.gate_up(x, w_gate, w_up, offsets, interpret=True, **how),
                  w_down, offsets, interpret=True, **how)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_float32_rows_take_the_same_walk():
    count, _, _, rows = CELLS["trinity"]
    _check("trinity", rows, _sizes("groups_straddle_windows", count, rows),
           dtype=jnp.float32)


@pytest.mark.parametrize("K,N,item,budget,tile", [
    (4096, 768, 2, ep._CHUNK_BYTES, 256),   # granite gate / up: three chunks
    (768, 4096, 2, ep._CHUNK_BYTES, 1024),  # granite down
    (5120, 1536, 2, ep._CHUNK_BYTES, 128),  # dots3 gate / up
    (1536, 5120, 2, ep._CHUNK_BYTES, 640),  # dots3 down
    (3072, 3072, 2, ep._CHUNK_BYTES, 256),  # Trinity
    (7168, 2048, 2, ep._CHUNK_BYTES, 128),  # deepseek gate / up
    (2048, 7168, 2, ep._CHUNK_BYTES, 512),  # deepseek down
    (7168, 2048, 2, 1 << 10, 128),          # never under one lane tile
])
def test_a_chunk_is_whole_lane_tiles_that_divide_the_columns(K, N, item,
                                                             budget, tile):
    got = ep.column_tile(K, N, item, budget)
    assert got == tile and N % got == 0 and got % ep.LANES == 0


@pytest.mark.parametrize("rows,K,N,dtype,ok", [
    (320, 4096, 768, jnp.bfloat16, True),
    (2048, 5120, 1536, jnp.bfloat16, True),
    (128, 7168, 2048, jnp.bfloat16, True),
    (8, 4096, 768, jnp.bfloat16, False),        # rows under a sublane tile
    (24, 4096, 768, jnp.float32, True),
    (320, 4096, 700, jnp.bfloat16, False),      # columns not in lane tiles
    (320, 32, 128, jnp.bfloat16, False),        # the tiny presets' widths
    (320, 4096, 768, jnp.int8, False),
])
def test_the_guard_takes_what_tiles_and_refuses_what_does_not(rows, K, N,
                                                              dtype, ok):
    assert ep.supports(rows, K, N, dtype) is ok
