"""models/expert_layer.py routes the rows that count and no other: the pairs
of an uncounted token (an idle slot's, a prompt's padding) join no held
expert's group. The layer alone under the three routers the cells run, then
each family's tiny preset through the engine with its other slots idle."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plugins
from kukeon_tpu.models import expert_layer as el
from kukeon_tpu.models import sparse_latent_moe as slm
from kukeon_tpu.models import ssm_moe as sm
from kukeon_tpu.models import window_moe as wm
from kukeon_tpu.obs import expo
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine
from tests import test_sparse_latent_moe as t_slm
from tests import test_ssm_moe as t_sm
from tests import test_window_moe as t_wm

N, H, I = 32, 32, 24
# the ways the cells route: (router width, held (first, count), the layer's
# keywords): Trinity's, deepseek's, granite's
ROUTERS = {
    "sigmoid_bias": (16, (4, 8), dict(experts_per_token=4, route_scale=2.448)),
    "sigmoid_groups": (32, (8, 16), dict(
        experts_per_token=6, route_scale=2.5, groups=8, groups_kept=4)),
    "softmax_selected": (24, (0, 12), dict(
        experts_per_token=5, scoring=el.SOFTMAX_SELECTED)),
}


def _layer(router: str, rows: int = N, held=None, widths=(H, I),
           dtype=jnp.float32):
    """(h [rows, H], the share's weights, the layer's keywords, every row's
    choices [rows, k] and which of them this share holds, the shared expert's
    output), the share ``held`` (first, count) where the router's own is not
    wanted; ``widths`` (H, I) and the activations' and experts' ``dtype``
    where the module's are not."""
    E, (first, count), kw = ROUTERS[router]
    H, I = widths
    first, count = held or (first, count)
    ks = jax.random.split(jax.random.key(len(router)), 9)
    n = jax.random.normal
    w = {"router": n(ks[0], (H, E)),
         "e_gate": n(ks[2], (count, H, I)) * H ** -.5,
         "e_up": n(ks[3], (count, H, I)) * H ** -.5,
         "e_down": n(ks[4], (count, I, H)) * I ** -.5,
         "s_gate": n(ks[5], (H, I)) * H ** -.5,
         "s_up": n(ks[6], (H, I)) * H ** -.5,
         "s_down": n(ks[7], (I, H)) * I ** -.5}
    if kw.get("scoring") != el.SOFTMAX_SELECTED:
        w["bias"] = 0.05 * n(ks[1], (E,))
    h = n(ks[8], (rows, H)).astype(dtype)
    w = {k: v if k in ("router", "bias") else v.astype(dtype)
         for k, v in w.items()}
    kw = dict(kw, experts_held=(first, count))
    route_kw = {k: v for k, v in kw.items()
                if k in ("groups", "groups_kept", "scoring")}
    sel, _ = el.route(h, w["router"], w.get("bias"), kw["experts_per_token"],
                      scale=kw.get("route_scale", 1.0), **route_kw)
    held = np.asarray((sel >= first) & (sel < first + count))
    shared = el.swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    return h, w, kw, np.asarray(sel), held, np.asarray(shared)


@pytest.fixture(params=sorted(ROUTERS))
def layer(request):
    return _layer(request.param)


def _some(seed=0):
    return np.random.default_rng(seed).random(N) < 0.4


def test_a_counted_rows_output_is_the_same_whoever_else_counts(layer):
    h, w, kw, _sel, _held, _shared = layer
    whole, _ = el.expert_layer_counts(h, w, counted=jnp.ones(N, bool), **kw)
    for seed in (0, 1):
        counted = _some(seed)
        y, _ = el.expert_layer_counts(h, w, counted=jnp.asarray(counted), **kw)
        np.testing.assert_allclose(np.asarray(y)[counted],
                                   np.asarray(whole)[counted], atol=1e-6)
    one = np.arange(N) == 7
    y, _ = el.expert_layer_counts(h, w, counted=jnp.asarray(one), **kw)
    np.testing.assert_allclose(np.asarray(y)[7], np.asarray(whole)[7],
                               atol=1e-6)


def test_an_uncounted_row_gets_the_shared_expert_alone(layer):
    h, w, kw, _sel, held, shared = layer
    counted = _some()
    y, _ = el.expert_layer_counts(h, w, counted=jnp.asarray(counted), **kw)
    np.testing.assert_allclose(np.asarray(y)[~counted], shared[~counted],
                               atol=1e-6)
    # and a counted row with a held choice gets more than that
    routed = counted & held.any(axis=1)
    assert routed.any()
    assert (np.abs(np.asarray(y) - shared)[routed].max(axis=1) > 1e-3).all()


def test_with_no_row_counted_every_group_is_empty_and_the_output_finite(layer):
    h, w, kw, _sel, _held, shared = layer
    y, tally = jax.jit(lambda h: el.expert_layer_counts(
        h, w, counted=jnp.zeros(N, bool), **kw))(h)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), shared, atol=1e-6)
    assert np.asarray(tally)[:len(el.TALLY)].tolist() == [
        0, kw["experts_held"][1], 0]


def test_one_counted_row_of_32_reaches_at_most_top_k_held_experts(layer):
    h, w, kw, sel, held, _shared = layer
    assert el.TALLY == ("kukeon_moe_held_hits_total",
                        "kukeon_moe_held_experts_total",
                        "kukeon_moe_held_experts_reached_total")
    row = int(np.argmax(held.sum(axis=1)))      # a row that hits something
    _, tally = el.expert_layer_counts(h, w, counted=jnp.arange(N) == row, **kw)
    hits, total, reached = (int(v) for v in tally[:len(el.TALLY)])
    assert total == kw["experts_held"][1]
    assert 0 < reached <= kw["experts_per_token"]
    # a row's choices are distinct experts: each hit reaches its own
    assert reached == hits == len(set(sel[row][held[row]]))
    _, tally = el.expert_layer_counts(h, w, counted=jnp.ones(N, bool), **kw)
    assert int(tally[2]) == len(set(sel[held])) <= total


def test_the_hit_count_is_the_counted_rows_held_choices(layer):
    h, w, kw, _sel, held, _shared = layer
    for counted in (np.ones(N, bool), _some(), np.zeros(N, bool)):
        _, tally = el.expert_layer_counts(h, w, counted=jnp.asarray(counted), **kw)
        assert int(tally[0]) == int(held[counted].sum())
    # leading axes as a decode step's [B, 1] and a prefill's [1, S]
    counted = _some()
    for lead in ((N, 1), (1, N)):
        y, tally = el.expert_layer_counts(h.reshape(*lead, H), w,
                                   counted=jnp.asarray(counted.reshape(lead)),
                                   **kw)
        assert y.shape == (*lead, H)
        assert int(tally[0]) == int(held[counted].sum())


# --- the routed products in blocks, against a plain sum -------------------------

def _plain(h, w, kw, sel, held, counted, shared):
    """float32, no sort and no ragged product: each counted row's own sum
    over its chosen held experts, weighted as the router weighs them."""
    route_kw = {k: v for k, v in kw.items()
                if k in ("groups", "groups_kept", "scoring")}
    _, wts = el.route(h, w["router"], w.get("bias"), kw["experts_per_token"],
                      scale=kw.get("route_scale", 1.0), **route_kw)
    h, wts = np.asarray(h, np.float64), np.asarray(wts, np.float64)
    first = kw["experts_held"][0]
    gate, up, down = (np.asarray(w[k], np.float64)
                      for k in ("e_gate", "e_up", "e_down"))
    out = np.asarray(shared, np.float64).copy()
    for row in np.flatnonzero(counted):
        for k in np.flatnonzero(held[row]):
            e = sel[row, k] - first
            g = h[row] @ gate[e]
            out[row] += wts[row, k] * ((g / (1 + np.exp(-g)) * (h[row] @ up[e]))
                                       @ down[e])
    return out


def _counted_with(held, pairs: int):
    """Rows whose held choices number ``pairs`` exactly."""
    counted, left = np.zeros(len(held), bool), pairs
    for row in np.argsort(-held.sum(axis=1), kind="stable"):
        if 0 < held[row].sum() <= left:
            counted[row] = True
            left -= held[row].sum()
    assert left == 0, (pairs, held.sum())
    return counted


# rows, the block's rows (None: the rule's own), every expert held, and the
# counted rows from which of them hold a chosen expert
BLOCKS = {
    "no pair held: zero trips": (32, 16, False, lambda held: np.zeros(32, bool)),
    "every pair held, the last block past them": (
        32, 28, True, lambda held: np.ones(32, bool)),
    "a multiple of the block": (32, 16, False, lambda held: _counted_with(held, 32)),
    "one more than a multiple": (32, 16, False, lambda held: _counted_with(held, 33)),
    "one counted row of 32": (
        32, 16, False, lambda held: np.arange(32) == np.argmax(held.sum(1))),
    "a decode step: the pairs under one block": (
        32, None, False, lambda held: np.arange(32) % 3 > 0),
    "a decode step that holds no pair: its one block all the same": (
        32, None, False, lambda held: np.zeros(32, bool)),
    "a piece of several blocks that do not divide it": (
        200, 56, False, lambda held: np.arange(200) % 5 > 0),
}


@pytest.mark.parametrize("router", ["sigmoid_groups", "softmax_selected"])
@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_the_blocked_products_are_the_plain_sum_and_count_what_they_walk(
        monkeypatch, router, case):
    rows, block, every, counted_of = BLOCKS[case]
    if block:
        monkeypatch.setattr(el, "BLOCK_ROWS", block)
    h, w, kw, sel, held, shared = _layer(
        router, rows, held=(0, ROUTERS[router][0]) if every else None)
    counted = counted_of(held)
    y, counts = el.expert_layer_counts(h, w, counted=jnp.asarray(counted),
                                       **kw)
    np.testing.assert_allclose(
        np.asarray(y), _plain(h, w, kw, sel, held, counted, shared),
        atol=2e-5)
    pairs = rows * kw["experts_per_token"]
    rows_a_block = el.block_rows(pairs)
    assert rows_a_block == min(block or el.BLOCK_ROWS, pairs)
    hits = int(held[counted].sum())
    assert el.COUNTS == el.TALLY + ("kukeon_moe_pair_rows_total",
                                    "kukeon_moe_pair_rows_worked_total")
    # whole blocks, as many as the held pairs fill; the one block that holds
    # every pair of a call is walked once whatever it holds
    worked = (pairs if rows_a_block == pairs
              else -(-hits // rows_a_block) * rows_a_block)
    assert np.asarray(counts).tolist() == [
        hits, kw["experts_held"][1], len(set(sel[counted][held[counted]])),
        pairs, worked]
    if "zero trips" in case:
        assert hits == 0
    if every:
        assert hits == pairs < int(counts[4])
    if "multiple" in case:
        assert hits % rows_a_block == ("one more" in case) and hits > block
    if "under one block" in case:
        assert 0 < hits < rows_a_block == pairs
    if "holds no pair" in case:
        assert hits == 0 and rows_a_block == pairs
    if "several blocks" in case:
        assert hits > 2 * rows_a_block and pairs % rows_a_block


# --- the same blocks through the Pallas products ---------------------------------

KERNEL_BLOCKS = {
    # rows, the block's rows (None: the rule's own), counted rows
    "a decode step": (32, None, lambda rows: np.arange(rows) % 3 > 0),
    "a decode step that holds no pair": (
        32, None, lambda rows: np.zeros(rows, bool)),
    "a piece of several blocks": (96, 144, lambda rows: np.arange(rows) % 5 > 0),
}


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("case", sorted(KERNEL_BLOCKS))
def test_the_kernels_and_the_ragged_products_agree_and_count_the_same(
        monkeypatch, router, case):
    """What a TPU runs (``ops/expert_products.py``, here interpreted) beside
    what every other backend runs, on bf16 rows at widths the kernel tiles:
    within one bf16 step of the output, every count the same."""
    from jax.experimental.pallas import tpu as pltpu

    from kukeon_tpu.ops import dispatch

    rows, block, counted_of = KERNEL_BLOCKS[case]
    if block:
        monkeypatch.setattr(el, "BLOCK_ROWS", block)
    h, w, kw, _, _, _ = _layer(router, rows, widths=(256, 128),
                               dtype=jnp.bfloat16)
    counted = jnp.asarray(counted_of(rows))
    before = dispatch.counts()
    y_xla, counts_xla = el.expert_layer_counts(h, w, counted=counted, **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        y, counts = el.expert_layer_counts(h, w, counted=counted, **kw)
    after = dispatch.counts()
    for impl in ("xla", "pallas"):
        assert (after[("expert_products", impl)]
                == before.get(("expert_products", impl), 0) + 1)
    assert y.dtype == y_xla.dtype == jnp.bfloat16
    assert np.asarray(counts).tolist() == np.asarray(counts_xla).tolist()
    y, y_xla = (np.asarray(v, np.float32) for v in (y, y_xla))
    assert np.all(np.isfinite(y))
    assert np.abs(y - y_xla).max() <= 2.0 ** -7 * max(1.0, np.abs(y_xla).max())
    if "no pair" in case:
        assert int(counts[0]) == 0
        np.testing.assert_array_equal(y, y_xla)
    if "several" in case:
        assert int(counts[4]) > el.block_rows(rows * kw["experts_per_token"])


def test_off_a_tpu_and_on_a_mesh_of_several_the_ragged_products_stay(
        monkeypatch):
    assert not el.kernel_runs(320, 4096, 768, jnp.bfloat16, 1)      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert el.kernel_runs(320, 4096, 768, jnp.bfloat16, 1)
    assert el.kernel_runs(2048, 5120, 1536, jnp.bfloat16, 0)   # no mesh set
    assert not el.kernel_runs(320, 4096, 768, jnp.bfloat16, 4)
    assert not el.kernel_runs(320, H, I, jnp.float32, 1)    # the tiny presets


# --- through the engine, each family's tiny preset ------------------------------

FAMILIES = {
    # the model, its test module (reference_config, SEED), cache rows, the
    # gap its own engine test allows, expert layers, held experts a layer
    "window_moe": (wm, wm.window_moe_tiny, t_wm, 64, 1e-4, 8, 4),
    "sparse_latent_moe": (slm, slm.sparse_latent_moe_tiny, t_slm, 128, 1e-4,
                          2, 4),
    "ssm_moe": (sm, sm.ssm_moe_tiny, t_sm, 128, t_sm.TOL, 8, 4),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_request_beside_idle_slots_and_a_padded_prompt_through_the_engine(
        family):
    """One request in slot 0 of four, its prompt of 16 tokens padded to a
    bucket of 32: every served token is the float32 reference's best at its
    position, the first token is the one an unpadded prefill gives, and the
    layer's tally is on the registry."""
    model, preset, tests_of, rows, tol, expert_layers, count = FAMILIES[family]
    cfg = preset()
    params = model.init_params(jax.random.key(tests_of.SEED), cfg)
    reference = plugins.load("reference", family)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    prompt = np.random.default_rng(4).integers(0, cfg.vocab_size, 16)

    def serve(slots, buckets, new):
        eng = ServingEngine(cfg, params, mesh, num_slots=slots,
                            max_seq_len=rows, decode_chunk=4,
                            prefill_buckets=buckets)
        req = eng.submit(prompt, SamplingParams(max_new_tokens=new))
        while not req.done.is_set():
            eng.step()
        return eng, list(req.generated)

    eng, generated = serve(4, (32, 64), 13)
    seq = np.concatenate([prompt, generated])
    pos = np.arange(len(prompt) - 1, len(seq) - 1)
    logits = reference.logits_at(tests_of.reference_config(cfg),
                                 tests_of.SEED, [seq], [pos], rows)[0]
    gaps = logits.max(-1) - logits[np.arange(len(pos)), seq[pos + 1]]
    assert gaps.max() < tol
    _, unpadded = serve(1, (16,), 1)
    assert unpadded[0] == generated[0]

    hits, total, reached = (eng.registry.get(name).value()
                            for name in el.TALLY)
    steps = sum(int(labels["k"]) * n for labels, n in eng.registry.get(
        "kukeon_engine_decode_chunks_total").samples())
    # one call a layer in the prefill and in every step of a fetched chunk
    calls, rest = divmod(total, expert_layers * count)
    assert rest == 0 and 1 + 12 <= calls <= 1 + steps
    # three of four slots idle: a step reaches no more than its one token hit
    assert 0 < reached <= min(total, hits)
    assert reached < total


# --- what a boot pays for the blocked products ----------------------------------

SETUP = {"sparse_latent_moe": (slm.sparse_latent_moe_tiny, 2),
         "mixed_latent_moe": (slm.mixed_latent_moe_tiny, 3)}


@pytest.mark.parametrize("preset", sorted(SETUP))
def test_a_boot_holds_the_programs_it_held_and_one_loop_an_expert_layer(
        monkeypatch, preset):
    """The families that unroll their layers pay for what ``_routed`` traces
    once a layer, bucket and boot pass. A boot and one request a bucket compile
    the programs 9e503f6 compiled there, as often (its own readings); and a
    lowered prefill whose pieces make more pairs than a block holds ONE loop
    more than the same program around a routed part without one, however
    many expert layers it unrolls (the routed part is a jitted function of
    its own: no copy a layer, no branch a capacity), a decode chunk none."""
    make, expert_layers = SETUP[preset]
    monkeypatch.setattr(el, "BLOCK_ROWS", 64)   # of a 32-row piece's 128 pairs
    cfg = make()
    params = slm.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])

    def engine():
        return ServingEngine(cfg, params, mesh, num_slots=2, max_seq_len=64,
                             decode_chunk=4, prefill_buckets=(16, 32))

    def loops(eng):
        key = jax.random.key(0)
        with jax.set_mesh(mesh):
            yield eng._prefill.lower(
                eng._abstract_params, jax.ShapeDtypeStruct((1, 32), jnp.int32),
                16, key, jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
            ).as_text().count("stablehlo.while")
            yield eng._decode_chunk.lower(
                eng._abstract_params, eng._abstract_state(), key,
                jnp.zeros(2, jnp.float32), jnp.zeros(2, jnp.int32),
                jnp.ones(2, jnp.float32), 4).as_text().count("stablehlo.while")

    eng = engine()
    eng.precompile((16, 32))
    for n in (9, 20):
        req = eng.submit(np.arange(1, n + 1), SamplingParams(max_new_tokens=6))
        while not req.done.is_set():
            eng.step()
    timed = dict(re.findall(
        r'kukeon_compile_seconds_count\{program="(\w+)"\} (\d+)',
        expo.render(eng.registry)))
    assert timed == {"prefill": "2", "insert": "2", "decode": "1"}
    with_loop = list(loops(eng))
    monkeypatch.setattr(el, "_routed", lambda h, *_, **__: (
        jnp.zeros(h.shape, jnp.float32), jnp.int32(0), jnp.int32(0)))
    without = list(loops(engine()))
    assert expert_layers > 1
    assert [a - b for a, b in zip(with_loop, without)] == [1, 0]
