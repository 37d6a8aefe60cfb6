import json
import os

import pytest

from benchmark import plugins

TRAFFIC = os.path.join(plugins.HERE, "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def _mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _gen(name, seed, seconds=45.0):
    mix = _mix(name)
    mod = plugins.load("generators", mix["generator"])
    return mix, mod.Generator(mix["params"], seed, 32768, seconds)


def _strip(reqs):
    return [(r["id"], round(r["due"], 9), tuple(r["prompt"]),
             r["max_new_tokens"], r["prefix_id"]) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_requests(name):
    _m, a = _gen(name, 3000000011)
    _m, b = _gen(name, 3000000011)
    _m, c = _gen(name, 12)
    assert _strip(a.arrivals()) == _strip(b.arrivals())
    assert _strip(a.arrivals()) != _strip(c.arrivals())


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work_at_the_same_moments(name):
    _m, a = _gen(name, 1)
    _m, b = _gen(name, 2)
    ra, rb = a.arrivals(), b.arrivals()
    assert [(r["id"], r["due"], len(r["prompt"]), r["max_new_tokens"])
            for r in ra] == [(r["id"], r["due"], len(r["prompt"]),
                              r["max_new_tokens"]) for r in rb]
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rb]


@pytest.mark.parametrize("name", MIXES)
def test_another_shape_seed_gives_another_schedule(name):
    mix = _mix(name)
    mod = plugins.load("generators", mix["generator"])
    a = mod.Generator(mix["params"], 1, 32768, 45.0).arrivals()
    other = {**mix["params"], "shape_seed": mix["params"]["shape_seed"] + 1}
    b = mod.Generator(other, 1, 32768, 45.0).arrivals()
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


@pytest.mark.parametrize("name", [m for m in MIXES if _mix(m)["generator"] == "independent"])
def test_independent_lengths_rate_and_clips(name):
    mix, g = _gen(name, 5)
    reqs = g.arrivals()
    p = mix["params"]
    assert len(reqs) == round(p["rate_per_s"] * 45.0)
    lo = min(x["min"] for x in p["prompt"]); hi = max(x["max"] for x in p["prompt"])
    alo = min(x["min"] for x in p["answer"]); ahi = max(x["max"] for x in p["answer"])
    assert all(lo <= len(r["prompt"]) <= hi for r in reqs)
    assert all(alo <= r["max_new_tokens"] <= ahi for r in reqs)
    assert all(0.0 <= r["due"] < 45.0 for r in reqs)
    assert all(r["prefix_id"] is None for r in reqs)
    assert all(0 <= t < 32768 for r in reqs for t in r["prompt"])
    assert g.on_complete(reqs[0], [1, 2], 1.0) == []


def test_sessions_grow_under_one_prefix_and_end_at_the_cap():
    mix, g = _gen("agent-sessions", 9)
    p = mix["params"]
    first = g.arrivals()
    assert len(first) == round(p["sessions_per_s"] * (45.0 + p["ramp_s"]))
    assert min(r["due"] for r in first) == pytest.approx(-p["ramp_s"])
    assert all(p["first_prompt"]["min"] <= len(r["prompt"]) <= p["first_prompt"]["max"]
               for r in first)
    r, turns = first[0], 1
    while True:
        answer = list(range(r["max_new_tokens"]))
        assert p["answer"]["min"] <= r["max_new_tokens"] <= p["answer"]["max"]
        nxt = g.on_complete(r, answer, 10.0)
        if not nxt:
            break
        (n,) = nxt
        assert n["prefix_id"] == r["prefix_id"]
        assert n["prompt"][:len(r["prompt"])] == r["prompt"]
        assert n["prompt"][len(r["prompt"]):len(r["prompt"]) + len(answer)] == answer
        tool = len(n["prompt"]) - len(r["prompt"]) - len(answer)
        assert p["tool"]["min"] <= tool <= p["tool"]["max"]
        assert n["new_tokens"] == len(answer) + tool
        assert len(n["prompt"]) <= p["max_prompt"]
        assert p["think_s"]["min"] <= n["due"] - 10.0 <= p["think_s"]["max"]
        r, turns = n, turns + 1
    assert 2 <= turns <= 16


def test_session_follow_ups_do_not_depend_on_completion_order():
    _m, a = _gen("agent-sessions", 4)
    _m, b = _gen("agent-sessions", 4)
    ra, rb = a.arrivals(), b.arrivals()
    x = [a.on_complete(r, [7] * r["max_new_tokens"], 1.0) for r in ra[:3]]
    y = [b.on_complete(r, [7] * r["max_new_tokens"], 1.0) for r in reversed(rb[:3])]
    assert _strip(sum(x, [])) == _strip(sum(reversed(y), []))
