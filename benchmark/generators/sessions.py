"""Agent sessions: a growing conversation per session, re-sent every turn.

A session's first prompt is ``first_prompt`` tokens; every later turn's prompt
is the previous prompt + the served answer + a tool result, due a think time
after the previous answer completed; ``prefixId`` is the session, so a server
that kept the previous prompt's KV recomputes only the tail. A session ends
when its next prompt would pass ``max_prompt``.

The sessions (their arrival times, lengths and think times) are drawn once from
the mix's own ``shape_seed``; the run's seed decides the tokens. So every seed
offers the same sessions at the same moments: with the order left to the run's
seed, the runs of one cell spread by 16-25% (PERF.md, PR 24). The same process
runs for ``ramp_s`` before the window opens, so that the window opens on
sessions in mid-course.

Parameters: ``sessions_per_s``, ``ramp_s``, ``first_prompt`` {min, max},
``tool`` and ``answer`` {median, sigma, min, max}, ``think_s`` {min, max},
``max_prompt``, ``shape_seed``.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators import _shapes


def scripts(params: dict, n: int) -> list[dict]:
    """n session scripts, the same for every run seed."""
    rng = np.random.default_rng([int(params["shape_seed"]), 7])
    fp, turns = params["first_prompt"], 16
    first = rng.permutation(np.rint(
        _shapes.uniform(n, fp["min"], fp["max"])).astype(int))
    draw = {k: rng.permutation(_shapes.lognormal(
        n * turns, params[k]["median"], params[k]["sigma"],
        params[k]["min"], params[k]["max"])).reshape(n, turns)
        for k in ("tool", "answer")}
    think = rng.permutation(_shapes.uniform(
        n * turns, params["think_s"]["min"], params["think_s"]["max"])
    ).reshape(n, turns)
    out = []
    for i in range(n):
        prompt, script = int(first[i]), []
        for t in range(turns):
            script.append({"prompt_len": prompt,
                           "answer": int(draw["answer"][i, t]),
                           "think_s": float(think[i, t])})
            prompt += int(draw["answer"][i, t]) + int(draw["tool"][i, t])
            if prompt > params["max_prompt"]:
                break
        out.append({"turns": script})
    return out


class Generator:
    def __init__(self, params: dict, seed: int, vocab: int, seconds: float):
        self.p, self.vocab = params, vocab
        span = params["ramp_s"] + seconds
        n = max(1, int(round(params["sessions_per_s"] * span)))
        shape = np.random.default_rng([int(params["shape_seed"]), 2])
        gaps = shape.permutation(_shapes.poisson_gaps(n, span))
        self._start = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            - params["ramp_s"]
        self._scripts = scripts(params, n)
        self._seed = int(seed)
        self._prompts: dict[int, list[int]] = {}

    def _tokens(self, s: int, turn: int, n: int) -> list[int]:
        rng = np.random.default_rng([self._seed, 3, s, turn])
        return rng.integers(0, self.vocab, n).tolist()

    def _request(self, s: int, turn: int, due: float, new: int) -> dict:
        step = self._scripts[s]["turns"][turn]
        return {"id": f"s{s}t{turn}", "due": due,
                "prefix_id": f"bench-{self._seed}-{s}",
                "prompt": list(self._prompts[s]), "new_tokens": new,
                "max_new_tokens": step["answer"], "session": s, "turn": turn}

    def arrivals(self) -> list[dict]:
        out = []
        for s, script in enumerate(self._scripts):
            n0 = script["turns"][0]["prompt_len"]
            self._prompts[s] = self._tokens(s, 0, n0)
            out.append(self._request(s, 0, float(self._start[s]), n0))
        return out

    def on_complete(self, request: dict, tokens: list[int],
                    done_at: float) -> list[dict]:
        s, turn = request["session"], request["turn"]
        turns = self._scripts[s]["turns"]
        if turn + 1 >= len(turns):
            return []
        want = turns[turn + 1]["prompt_len"]
        have = len(self._prompts[s]) + len(tokens)
        tool = self._tokens(s, turn + 1, max(1, want - have))
        self._prompts[s] = self._prompts[s] + list(tokens) + tool
        return [self._request(s, turn + 1, done_at + turns[turn]["think_s"],
                              len(tokens) + len(tool))]
