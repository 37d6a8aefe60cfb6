"""The engine's chunk rule (serving/engine.py ``_chunk_size``): a decode chunk
runs 4 steps while a slot is free after this step's admissions, decode_chunk
steps once every slot is seated, and never past the cache's capacity. The same
programs in shorter chunks: same tokens, no compile after warmup(), and
``kukeon_engine_decode_chunks_total{k}`` says how often each length ran."""

import time

import jax
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.obs import render
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import SamplingParams, ServingEngine
from kukeon_tpu.serving.engine import _InflightChunk

ROWS = 128
# (prompt length, max_new_tokens): limits that fall inside a chunk of 4 and
# inside one of 16, on either side of a chunk boundary.
REQUESTS = [(8, 3), (11, 6), (5, 9), (14, 18), (9, 23), (12, 30), (7, 4)]


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params, make_mesh(tensor=1, devices=jax.devices()[:1])


def _engine(tiny, num_slots):
    cfg, params, mesh = tiny
    return ServingEngine(cfg, params, mesh, num_slots=num_slots,
                         max_seq_len=ROWS, decode_chunk=16)


def _chunks(eng) -> dict[int, int]:
    return {int(lab["k"]): int(v) for lab, v in eng._m_chunks.samples()}


def _prompt(i, n):
    return (np.arange(n, dtype=np.int32) * (i + 3) + i) % 200 + 1


# --- the rule itself ----------------------------------------------------------

@pytest.mark.parametrize("seated, want", [(1, 4), (2, 4), (3, 16)])
def test_chunk_is_short_while_a_slot_is_free_and_whole_once_all_are_seated(
        tiny, seated, want):
    """Through submit() and step(): the admit loop has emptied the queue into
    the slots, so an EMPTY queue with a slot free is the case the rule is for."""
    eng = _engine(tiny, num_slots=3)
    reqs = [eng.submit(_prompt(i, 8), SamplingParams(max_new_tokens=40))
            for i in range(seated)]
    eng.step()
    assert eng._pending.empty() and not eng._resume
    assert len(eng._free_slots()) == 3 - seated
    assert eng._inflight.k == want          # the chunk that step dispatched
    assert eng._chunk_size() == want        # and the next one
    assert _chunks(eng) == {want: 1}
    for r in reqs:
        r.cancel()
    while not all(r.done.is_set() for r in reqs):
        eng.step()


@pytest.mark.parametrize("free, inflight_k, room, want", [
    (0, 0, 40, 16), (0, 0, 16, 16), (0, 0, 15, 4), (0, 0, 5, 4), (0, 0, 3, 1),
    (0, 0, 0, 1),           # a full slot still gets its (discarded) step
    (0, 16, 40, 16), (0, 16, 31, 4), (0, 4, 7, 1),   # the unflushed chunk counts
    (1, 0, 40, 4), (1, 0, 3, 1), (1, 4, 7, 1),
])
def test_capacity_clamp_rounds_down_to_a_power_of_four(tiny, free, inflight_k,
                                                       room, want):
    """``room`` rows are left in the fullest seated slot, ``inflight_k`` of
    them already written on the device by the chunk not yet flushed."""
    eng = _engine(tiny, num_slots=2)
    seated = 2 - free
    for slot in range(seated):
        eng._slot_req[slot] = object()
        eng._slot_len[slot] = 10
    eng._slot_len[0] = ROWS - room
    if inflight_k:
        eng._inflight = _InflightChunk(tokens=None, k=inflight_k, slots=[])
    assert eng._chunk_size() == want


# --- the same tokens, no compile, the counter ---------------------------------

@pytest.fixture(scope="module")
def served(tiny):
    """REQUESTS one at a time (a slot is always free: every chunk is 4), then
    all at once through start() / submit() on 4 slots (seven requests for four
    slots: whole chunks while all are seated, short ones once the queue has
    drained and a slot stays free)."""
    eng = _engine(tiny, num_slots=4)
    eng.warmup(8)
    compiles0 = {p: eng.compiles.count(p)
                 for p in ("prefill", "insert", "decode")}
    serial = [eng.generate(_prompt(i, n), SamplingParams(max_new_tokens=new))
              for i, (n, new) in enumerate(REQUESTS)]
    after_serial = _chunks(eng)
    reqs = [eng.submit(_prompt(i, n), SamplingParams(max_new_tokens=new))
            for i, (n, new) in enumerate(REQUESTS)]
    eng.start()
    try:
        deadline = time.monotonic() + 120
        for r in reqs:
            assert r.done.wait(timeout=max(0.0, deadline - time.monotonic()))
    finally:
        eng.stop()
    return {"eng": eng, "compiles0": compiles0, "serial": serial,
            "after_serial": after_serial,
            "concurrent": [r.generated for r in reqs],
            "errors": [r.error for r in reqs]}


def test_tokens_under_the_rule_equal_the_same_requests_one_at_a_time(served):
    assert served["errors"] == [None] * len(REQUESTS)
    assert served["concurrent"] == served["serial"]


@pytest.mark.parametrize("which", ["serial", "concurrent"])
def test_a_finish_inside_a_chunk_keeps_every_token_before_the_limit(served,
                                                                    which):
    assert [len(g) for g in served[which]] == [new for _n, new in REQUESTS]


def test_the_run_mixed_chunks_of_4_and_16(served):
    assert set(served["after_serial"]) == {4}
    got = _chunks(served["eng"])
    assert set(got) == {4, 16}
    assert got[4] > served["after_serial"][4]     # short ones while it drained


def test_no_program_compiles_after_warmup_over_chunks_of_4_and_16(served):
    eng = served["eng"]
    assert {p: eng.compiles.count(p) for p in served["compiles0"]} \
        == served["compiles0"]


def test_the_counter_grows_by_one_a_dispatched_chunk_under_its_length(tiny):
    eng = _engine(tiny, num_slots=2)
    a = eng.submit(_prompt(0, 8), SamplingParams(max_new_tokens=60))
    eng.step()
    assert _chunks(eng) == {4: 1}
    eng.step()
    assert _chunks(eng) == {4: 2}
    b = eng.submit(_prompt(1, 8), SamplingParams(max_new_tokens=60))
    eng.step()                                # both slots seated
    assert _chunks(eng) == {4: 2, 16: 1}
    b.cancel()
    eng.step()                                # the sweep frees b's slot
    assert _chunks(eng) == {4: 3, 16: 1}
    assert sum(_chunks(eng).values()) == eng.sync_stats["chunks"]
    # on the scrape, under the label
    text = render(eng.registry)
    assert 'kukeon_engine_decode_chunks_total{k="4"} 3' in text
    assert 'kukeon_engine_decode_chunks_total{k="16"} 1' in text
    a.cancel()
    while not a.done.is_set():
        eng.step()
