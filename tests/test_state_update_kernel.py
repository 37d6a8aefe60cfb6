"""A decode step's update of the held scan states (ops/selective_scan.py
``update_held``): the Pallas kernel that walks the ACTIVE slots of the stack,
in interpret mode on the CPU, against ``selective_scan._step`` (tests/
test_chip_compile.py compiles it for the chip), the XLA body the CPU takes,
and the engine on both.

Tolerance: float32 on both sides, the same recurrence in the same order but
for the sum over the states, so 1e-5 on values of order 1-10. What must be
EXACT is exact: an idle slot's state, every other mixer's slice, an idle
slot's zero ``y``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kukeon_tpu.models import kv_kinds
from kukeon_tpu.models import ssm_hybrid as sh
from kukeon_tpu.ops import dispatch
from kukeon_tpu.ops import selective_scan as ss
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine

MIXERS, SLOTS, STATES, CHANNELS = 3, 8, 8, 256
MASKS = {"none": [0] * 8, "one": [0, 0, 0, 1, 0, 0, 0, 0],
         "alternating": [1, 0] * 4, "all": [1] * 8}


def _inputs(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    held = jax.random.normal(ks[0], (MIXERS, SLOTS, STATES, CHANNELS))
    c, z = (jax.random.normal(k, (SLOTS, CHANNELS)) for k in ks[1:3])
    d = jax.nn.softplus(jax.random.normal(ks[3], (SLOTS, CHANNELS)) - 3.0)
    b, cm = (jax.random.normal(k, (SLOTS, STATES)) for k in ks[4:])
    a = -jnp.broadcast_to(
        jnp.arange(1, STATES + 1, dtype=jnp.float32)[:, None],
        (STATES, CHANNELS))
    return held, (c, d, z, b, cm, a, jnp.ones((CHANNELS,)))


def _check(held, layer, active, y, new, step_inputs):
    """(y, new) is one step of mixer ``layer`` for the ``active`` slots and
    nothing else: returns the idle slots' y."""
    held, y, new, on = (np.asarray(x) for x in (held, y, new, active))
    want_h, want_y = ss._step(held[layer], *step_inputs)
    np.testing.assert_allclose(new[layer][on], np.asarray(want_h)[on],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y[on], np.asarray(want_y)[on], atol=1e-5,
                               rtol=1e-5)
    assert np.array_equal(new[layer][~on], held[layer][~on])
    others = [m for m in range(MIXERS) if m != layer]
    assert np.array_equal(new[others], held[others])
    return y[~on]


@pytest.mark.parametrize("mask", list(MASKS))
def test_the_kernel_steps_the_active_slots_of_one_mixer_and_touches_nothing_else(
        mask):
    """Interpret mode, the mixer's index traced: h' and y of the active slots
    are ``_step``'s, an idle slot's state and every other mixer's slice are
    bit-identical, y is exactly zero where a slot is idle."""
    held, step_inputs = _inputs()
    active = jnp.asarray(MASKS[mask], bool)
    walk = ss.live_slots(active)
    assert int(walk.live[0]) == sum(MASKS[mask])
    assert list(np.asarray(walk.slots)[:sum(MASKS[mask])]) == [
        i for i, on in enumerate(MASKS[mask]) if on]
    step = jax.jit(lambda held, layer: ss.update_kernel(
        held, layer, walk.slots, walk.live, *step_inputs, interpret=True))
    for layer in (1, 2):
        y, new = step(held, jnp.int32(layer))
        assert not _check(held, layer, active, y, new, step_inputs).any()


@pytest.mark.parametrize("mask", list(MASKS))
def test_off_a_tpu_the_update_is_the_xla_body_and_says_so(mask):
    """``update_held`` on the CPU: the same step, an idle slot's state kept by
    the select in the write; the choice is counted as the prefill scan's is."""
    held, step_inputs = _inputs(seed=1)
    active = jnp.asarray(MASKS[mask], bool)
    assert not ss.update_kernel_runs(5120, 16, 1)       # no TPU here
    before = dispatch.counts().get(("state_update", "xla"), 0)
    y, new = jax.jit(lambda held, layer: ss.update_held(
        held, layer, ss.live_slots(active), *step_inputs))(
            held, jnp.int32(2))
    assert dispatch.counts()[("state_update", "xla")] == before + 1
    _check(held, 2, active, y, new, step_inputs)


@pytest.mark.parametrize("mask", list(MASKS))
def test_where_the_kernel_runs_it_is_the_one_body_at_every_occupancy(
        monkeypatch, mask):
    """``update_held`` as on one TPU (the test steers the choice; the kernel
    in interpret mode): the walk whatever is live, none to all, an idle
    slot's y zero, the same step for the active slots and nothing else
    touched."""
    held, step_inputs = _inputs(seed=2)
    active = jnp.asarray(MASKS[mask], bool)
    monkeypatch.setattr(ss, "update_kernel_runs", lambda *_: True)
    monkeypatch.setattr(ss, "update_kernel", functools.partial(
        ss.update_kernel, interpret=True))
    before = dispatch.counts().get(("state_update", "pallas"), 0)
    y, new = jax.jit(lambda held, layer, active: ss.update_held(
        held, layer, ss.live_slots(active), *step_inputs))(
            held, jnp.int32(0), active)
    assert dispatch.counts()[("state_update", "pallas")] == before + 1
    assert not _check(held, 0, active, y, new, step_inputs).any()


def test_a_decode_step_on_the_cpu_notes_the_xla_body():
    """The whole ``decode`` of the tiny preset: both runs of mixers trace the
    update once, each notes ("state_update", "xla"), and none the kernel: a
    silent fall back on the chip would show in
    ``kukeon_op_impl_traces_total{op="state_update"}`` the same way."""
    cfg = sh.ssm_hybrid_tiny()
    params = jax.eval_shape(lambda: sh.init_params(jax.random.key(0), cfg))
    kinds = cfg.cache_kinds(64)
    held = kv_kinds.shapes(kinds, 2, cfg.num_kv_heads, cfg.head_dim, cfg.dtype)
    before = dispatch.counts()
    jax.eval_shape(
        lambda p, t, held, on: sh.decode(p, cfg, t, kv_kinds.view(held),
                                         kinds, on),
        params, jax.ShapeDtypeStruct((2,), jnp.int32), held,
        jax.ShapeDtypeStruct((2,), bool))
    after = dispatch.counts()
    assert after[("state_update", "xla")] == before.get(
        ("state_update", "xla"), 0) + 2
    assert after.get(("state_update", "pallas"), 0) == before.get(
        ("state_update", "pallas"), 0)


def _served(cfg, params):
    """The engine's own prefill, insert and decode_chunk, two slots: a second
    request is admitted while the first decodes, the first finishes, a third
    takes its slot over. Returns the three answers and the final states."""
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    eng = ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=128,
                        decode_chunk=4, prefill_buckets=(16, 32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 19, 2)]
    reqs = [eng.submit(prompts[0], SamplingParams(max_new_tokens=14))]
    for _ in range(2):
        eng.step()
    assert not reqs[0].done.is_set()
    reqs.append(eng.submit(prompts[1], SamplingParams(max_new_tokens=24)))
    while not reqs[0].done.is_set():
        eng.step()
    reqs.append(eng.submit(prompts[2], SamplingParams(max_new_tokens=9)))
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    assert reqs[2].slot == reqs[0].slot != reqs[1].slot
    return ([list(r.generated) for r in reqs],
            np.asarray(eng.state.cache.held[0]["ssm"]))


def test_the_engine_serves_the_same_tokens_over_the_kernel_as_over_the_xla_body(
        monkeypatch):
    """Two slots admitted at different steps, one released and reseated: the
    decode programs built over the kernel (interpret mode; the test steers the
    choice, no option of the program does) give the tokens the XLA body gives,
    and leave the same states behind to float32's rounding."""
    cfg = sh.ssm_hybrid_tiny()
    params = sh.init_params(jax.random.key(7), cfg)
    want, want_states = _served(cfg, params)
    monkeypatch.setattr(ss, "update_kernel_runs", lambda *_: True)
    monkeypatch.setattr(ss, "update_kernel", functools.partial(
        ss.update_kernel, interpret=True))
    before = dispatch.counts().get(("state_update", "pallas"), 0)
    got, got_states = _served(cfg, params)
    assert dispatch.counts()[("state_update", "pallas")] > before
    assert got == want
    assert [len(g) for g in got] == [14, 24, 9]
    np.testing.assert_allclose(got_states, want_states, atol=1e-5, rtol=1e-5)
