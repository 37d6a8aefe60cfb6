"""``models/kv_kinds.py``: a RING of rows of named arrays (a window layer's
own latent row), alone and beside a second kind of named arrays of other
widths (a selecting layer's key and row), and a ring that HOLDS more rows than
the window it attends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import kv_kinds
from kukeon_tpu.ops import sparse_attention as sa

LATENT = kv_kinds.CacheKind("latent", (0, 1), 64,
                            arrays=(("kidx", 4), ("ckv", 12)), select=5)
RING = kv_kinds.CacheKind("window_latent", (2, 3, 4), 8, ring=True,
                          arrays=(("wckv", 6),), window=7)
KINDS = (LATENT, RING)


def _empty(kinds, slots):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        kv_kinds.shapes(kinds, slots, 2, 8, jnp.float32))


def _block(S, rng):
    """What a prefill leaves: each named array over ITS kind's layers, the
    value of a row its position (+ 100 a layer) so that a test can read off
    which position landed where."""
    def rows(layers, width):
        pos = np.arange(S, dtype=np.float32)[None, None, :, None]
        layer = 100 * np.arange(layers, dtype=np.float32)[:, None, None, None]
        return jnp.asarray(np.broadcast_to(pos + layer, (layers, 1, S, width)))

    return {"kidx": rows(2, 4), "ckv": rows(2, 12), "wckv": rows(3, 6)}


def test_two_kinds_of_named_arrays_lie_side_by_side():
    assert kv_kinds.names(KINDS) == ("ckv", "kidx", "wckv")
    assert (RING.unit, RING.row_names()) == ("rows", ("wckv",))
    shapes = kv_kinds.shapes(KINDS, 3, 2, 8, jnp.float32)
    assert [{k: v.shape for k, v in h.items()} for h in shapes.held] == [
        {"kidx": (2, 3, 64, 4), "ckv": (2, 3, 64, 12)},
        {"wckv": (3, 3, 8, 6)}]
    # no head axis to swap in either
    cache = _empty(KINDS, 3)
    assert kv_kinds.view(cache).held[1]["wckv"].shape == (3, 3, 8, 6)
    assert (RING.live(3), RING.live(40), RING.read(40)) == (3, 8, 8)


@pytest.mark.parametrize("length, S", [
    (3, 16),        # below the held rows: rows 0-2
    (8, 16),        # at them: every row, none wrapped
    (9, 16),        # one past: row 0 holds position 8
    (21, 32),       # wrapped twice and a part
    (32, 32),       # the whole bucket, a multiple of the ring
    (5, 4 + 4)])    # a bucket as short as the ring: no gather
def test_insert_takes_for_each_ring_row_the_last_position_that_lands_there(
        length, S):
    block = _block(S, np.random.default_rng(0))
    cache = kv_kinds.insert(_empty(KINDS, 3), KINDS, block, length, 1)
    assert cache.lengths.tolist() == [0, length, 0]
    ring = np.asarray(cache.held[1]["wckv"])
    assert ring.shape == (3, 3, 8, 6)
    for r in range(8):
        at = [p for p in range(length) if p % 8 == r]
        if at:      # the LAST position below the length that lands in row r
            for layer in range(3):
                assert (ring[layer, 1, r] == at[-1] + 100 * layer).all()
    assert not ring[:, 0].any() and not ring[:, 2].any()
    # the other kind beside it keeps every row where its position says
    ckv = np.asarray(cache.held[0]["ckv"])
    assert (ckv[1, 1, :S, 0] == 100 + np.arange(S)).all()


def test_append_writes_at_position_mod_rows_across_a_wrap():
    cache = kv_kinds.insert(_empty(KINDS, 2), KINDS,
                            _block(16, np.random.default_rng(0)), 6, 0)
    active = jnp.array([True, False])
    for step in range(6, 20):       # crosses row 7 -> row 0, twice
        new = {"kidx": jnp.full((2, 2, 1, 4), -1.0),
               "ckv": jnp.full((2, 2, 1, 12), -2.0),
               "wckv": jnp.full((3, 2, 1, 6), float(step))}
        cache = kv_kinds.append(cache, KINDS, new, active)
        assert cache.lengths.tolist() == [step + 1, 0]
        ring = np.asarray(cache.held[1]["wckv"])[:, 0, :, 0]
        for r in range(min(step + 1, 8)):      # the rows some position reached
            last = max(p for p in range(step + 1) if p % 8 == r)
            want = last if last >= 6 else last + 100 * np.arange(3)
            assert (ring[:, r] == want).all(), (step, r)
    assert (np.asarray(cache.held[0]["ckv"])[:, 0, 6:20] == -2.0).all()


@pytest.mark.parametrize("rows, window", [(8, 9), (8, 7), (8, 3), (16, 10)])
def test_ring_keep_names_the_window_where_a_ring_holds_more(rows, window):
    """Position p lives in row p mod rows; a step at position t attends the
    ``window - 1`` positions before it (its own row takes part unwritten),
    wherever the ring has wrapped to. ``valid`` says it in two numbers where
    it can, and refuses where it cannot."""
    kd = kv_kinds.CacheKind("w", (0,), rows, ring=True, arrays=(("a", 4),),
                            window=window)
    lengths = jnp.arange(0, 5 * rows + 3)
    got = np.asarray(sa.ring_keep(lengths, kd.rows, kd.window))
    for t in range(got.shape[0]):
        want = np.zeros(rows, bool)
        for p in range(max(0, t - (window - 1)), t):
            want[p % rows] = True
        np.testing.assert_array_equal(got[t], want, err_msg=str(t))
    if window - 1 == rows:      # it holds the positions behind and no other
        count, skip = kv_kinds.valid(kd, lengths)
        assert skip is None
        np.testing.assert_array_equal(count, np.minimum(lengths, rows))
        np.testing.assert_array_equal(got.sum(1), count)
    else:
        with pytest.raises(ValueError, match="ring_keep"):
            kv_kinds.valid(kd, lengths)


def test_valid_and_ring_keep_agree_on_a_ring_of_k_and_v_that_holds_its_window():
    """The K / V ring holds ``rows`` = its window: a count and the one row
    the step's own token is about to take."""
    kd = kv_kinds.CacheKind("window", (0,), 8, ring=True)
    lengths = jnp.arange(0, 30)
    count, skip = kv_kinds.valid(kd, lengths)
    mask = np.asarray(sa.ring_keep(lengths, kd.rows, kd.rows))
    for t in range(30):
        want = np.arange(8) < int(count[t])
        if t >= 8:
            want[int(skip[t])] = False
        else:       # before the wrap the skipped row is past the rows read
            assert int(skip[t]) >= int(count[t]) or t == 0
        np.testing.assert_array_equal(mask[t], want, err_msg=str(t))
