"""A request's time by phase over a window of /metrics (serving/engine.py
beside obs/trace.py): the histograms the engine observes where a wait ends
(queue wait, time to first token, token gap, end to end) hold the sums of the
same requests' ``phasesS`` in the ring, which ``Span.phases_s()`` derives; a
request that never reached a wait's end is not in that wait's histogram; and
the span arguments a capture needs to say how much decoding slot-time waited
behind prefills (``decoding``, ``active``) carry a scripted schedule."""

import threading
import time

import jax
import jax.profiler
import numpy as np
import pytest

from kukeon_tpu.models import llama
from kukeon_tpu.obs import Span, Tracer
from kukeon_tpu.parallel import make_mesh
from kukeon_tpu.serving import RejectedError, SamplingParams, ServingEngine


def _engine(**kw):
    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    mesh = make_mesh(tensor=1, devices=jax.devices()[:1])
    kw.setdefault("num_slots", 2)
    return ServingEngine(cfg, params, mesh, max_seq_len=256, decode_chunk=4,
                         **kw)


def _submit(eng, n, new=5, **kw):
    return eng.submit(np.arange(1, n + 1, dtype=np.int32),
                      SamplingParams(max_new_tokens=new), **kw)


def _drain(eng, *reqs):
    deadline = time.monotonic() + 120
    while not all(r.done.is_set() for r in reqs):
        eng.step()
        assert time.monotonic() < deadline


def _observed(eng) -> dict[str, tuple[float, int]]:
    """(sum, count) of the four histograms a request's waits end in."""
    return {name: h.snapshot()[1:] for name, h in (
        ("queue_wait", eng._m_queue_wait), ("ttft", eng._m_ttft),
        ("token_gap", eng._m_itl), ("e2e", eng._m_e2e))}


class _Recorded:
    """Stands in for jax.profiler.TraceAnnotation: keeps every span's name
    and arguments, those set while it was open too."""

    events: list = []
    lock = threading.Lock()

    def __init__(self, name, **args):
        self.event = (name, dict(args))

    def __enter__(self):
        with self.lock:
            self.events.append(self.event)
        return self

    def set_metadata(self, **args):
        self.event[1].update(args)

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorded(monkeypatch):
    _Recorded.events = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorded)
    return _Recorded.events


def _named(events, name):
    return [args for n, args in events if n == name]


@pytest.mark.parametrize("paged", [False, True])
def test_the_histograms_hold_the_sums_of_the_rings_phases(paged):
    eng = _engine(**(dict(kv_page_tokens=16, kv_pool_pages=64)
                     if paged else {}))
    _drain(eng, _submit(eng, 12))                 # compile outside the deltas
    base = _observed(eng)
    # five requests over two slots: three of them queue behind the others
    reqs = [_submit(eng, n, new=new)
            for n, new in ((8, 5), (70, 9), (20, 1), (33, 6), (130, 4))]
    _drain(eng, *reqs)
    got = {k: (s - base[k][0], n - base[k][1])
           for k, (s, n) in _observed(eng).items()}
    spans = {s["requestId"]: s for s in eng.tracer.recent(10)}
    want = dict.fromkeys(
        ("queued", "prefill_dispatch", "prefill_wait", "decode"), 0.0)
    for r in reqs:
        for phase, s in spans[r.id]["phasesS"].items():
            want[phase] += s
    assert want["queued"] > 0 and want["decode"] > 0
    # A histogram's instant and the chain's event are two reads of the clock
    # a line apart, and the chain's end comes after the slot's release.
    near = dict(abs=len(reqs) * 2e-3)
    assert got["queue_wait"] == (pytest.approx(want["queued"], **near), 5)
    assert got["ttft"] == (pytest.approx(
        want["queued"] + want["prefill_dispatch"] + want["prefill_wait"],
        **near), 5)
    assert got["token_gap"] == (pytest.approx(want["decode"], **near),
                                4 + 8 + 0 + 5 + 3)
    assert got["e2e"] == (pytest.approx(sum(want.values()), **near), 5)


def _end_shed(eng):
    held = _submit(eng, 8)
    with pytest.raises(RejectedError):
        _submit(eng, 8)
    held.cancel()
    _drain(eng, held)
    # neither left the queue for a slot: no wait of theirs ended
    return "shed", {"queue_wait": 0, "ttft": 0, "token_gap": 0}


def _end_timeout(eng):
    req = _submit(eng, 8, new=200, deadline_s=0.05)
    eng.step()                                    # seated and decoding
    time.sleep(0.06)
    _drain(eng, req)
    assert req.timed_out
    return "timeout", {"queue_wait": 1, "ttft": 1,
                       "token_gap": len(req.generated) - 1}


def _end_cancelled(eng):
    req = _submit(eng, 8, new=200)
    eng.step()
    req.cancel()
    _drain(eng, req)
    return "cancelled", {"queue_wait": 1, "ttft": 1,
                         "token_gap": len(req.generated) - 1}


@pytest.mark.parametrize("end", [_end_shed, _end_timeout, _end_cancelled])
def test_a_request_is_in_the_histograms_of_the_waits_it_reached_the_end_of(
        end):
    eng = _engine(max_pending=1)
    outcome, want = end(eng)
    assert eng._m_requests.value(outcome=outcome) == 1
    assert eng._m_requests.value(outcome="ok") == 0
    got = _observed(eng)
    assert {k: got[k][1] for k in want} == want
    # the ring shows where each of them stopped
    assert outcome in {s["outcome"] for s in eng.tracer.recent(10)}


def test_phases_s_is_what_to_dict_rounds_and_sums_a_phase_entered_twice():
    span = Span(request_id=7, prompt_tokens=3, start_mono=10.0)
    for name, at in (("admitted", 10.5), ("prefill_dispatched", 10.75),
                     ("first_token", 11.0), ("preempted", 12.0),
                     ("admitted", 12.25), ("prefill_dispatched", 12.5),
                     ("finished", 14.0)):
        span.event(name, at=at)
    assert span.phases_s() == {
        "queued": 0.5, "prefill_dispatch": 0.5, "prefill_wait": 1.75,
        "decode": 1.0, "preempted": 0.25}
    assert span.to_dict()["phasesS"] == span.phases_s()
    assert sum(span.phases_s().values()) == span.e2e_s == 4.0
    # another component's span keeps its raw event names
    hop = Span(request_id=1, prompt_tokens=0, component="gateway",
               start_mono=1.0)
    hop.event("proxy_attempt", at=1.5)
    hop.event("finished", at=2.0)
    assert hop.phases_s() == {"submitted": 0.5, "proxy_attempt": 0.5}


def test_phases_s_of_a_chain_that_stopped_early_holds_what_it_reached():
    tracer = Tracer()
    span = tracer.begin(3, 5, start_mono=time.monotonic() - 0.25)
    tracer.finish(span, "shed")
    assert list(span.phases_s()) == ["queued"]
    assert span.phases_s()["queued"] == pytest.approx(0.25, abs=0.05)
    assert span.to_dict()["phasesS"] == {
        "queued": round(span.phases_s()["queued"], 6)}


def test_decoding_and_active_carry_a_scripted_schedule(recorded):
    eng = _engine(num_slots=3)
    a, b = _submit(eng, 8, new=40), _submit(eng, 9, new=40)
    eng.step()          # both prefilled and seated, their first chunk out
    eng.step()
    prefills = _named(recorded, "engine.prefill_dispatch")
    # a, seated by the same admit, was not decoding yet when b's prefill ran
    assert [p["decoding"] for p in prefills] == [0, 0]
    c = _submit(eng, 10, new=3)
    eng.step()          # c's prefill is dispatched beside two decoding slots
    assert _named(recorded, "engine.prefill_dispatch")[-1]["decoding"] == 2
    _drain(eng, c)
    # a request seated into the slot that c left finds the same two decoding
    d = _submit(eng, 11, new=2)
    _drain(eng, d)
    assert _named(recorded, "engine.prefill_dispatch")[-1]["decoding"] == 2
    _drain(eng, a, b)
    chunks = _named(recorded, "engine.fetch_chunk")
    assert all(ch["k"] in (1, 4) for ch in chunks)
    # two slots stepped until c was seated, three while it decoded
    assert [ch["active"] for ch in chunks[:3]] == [2, 2, 3]
    assert {ch["active"] for ch in chunks} == {2, 3}


def test_an_exports_prefill_counts_the_decoding_slots_too(recorded):
    eng = _engine()
    a = _submit(eng, 8, new=30)
    eng.step()
    eng.step()
    out = _submit(eng, 20, export=True)
    _drain(eng, out)
    spans = _named(recorded, "engine.prefill_dispatch")
    assert (spans[-1]["slot"], spans[-1]["decoding"]) == (-1, 1)
    a.cancel()
    _drain(eng, a)
