"""Independent requests: unshared prompts, no prefixId, Poisson arrivals.

The schedule (arrival times, prompt and answer lengths) is drawn once from the
mix's own ``shape_seed``; the run's seed decides the tokens. So every seed
offers the same work at the same moments and two runs differ only in what the
system does with it: with the order left to the run's seed, the runs of one
cell spread by 16-25% (PERF.md, PR 24), which no bound could hold.

Parameters (the mix's ``params``): ``rate_per_s``, ``shape_seed``; ``prompt``
and ``answer``, each a list of lognormal parts {share, median, sigma, min, max}.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators import _shapes


class Generator:
    def __init__(self, params: dict, seed: int, vocab: int, seconds: float):
        n = max(1, int(round(params["rate_per_s"] * seconds)))
        shape = np.random.default_rng([int(params["shape_seed"]), 1])
        gaps = shape.permutation(_shapes.poisson_gaps(n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        prompts = shape.permutation(_shapes.mixture(n, params["prompt"]))
        answers = shape.permutation(_shapes.mixture(n, params["answer"]))
        rng = np.random.default_rng([int(seed), 1])
        self._requests = [{
            "id": f"r{i}", "due": float(due[i]), "prefix_id": None,
            "prompt": rng.integers(0, vocab, int(prompts[i])).tolist(),
            "max_new_tokens": int(answers[i]), "new_tokens": int(prompts[i]),
        } for i in range(n)]

    def arrivals(self) -> list[dict]:
        return list(self._requests)

    def on_complete(self, request: dict, tokens: list[int],
                    done_at: float) -> list[dict]:
        return []
