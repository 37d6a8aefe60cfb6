"""What the readers of the program's own spans and counters share
(kukeon_tpu/obs/spans.py; the counter families of serving/engine.py).

THE CONTRACT OF THESE READERS. The driver runs this directory over the parent
commit's program as well as over the change's, and a later PR's parent may be
any commit. So a reader here returns None, and never raises or exits, on a
program without the span or the counter it reads: when the capture holds no
``engine.*`` event; when a counter family is absent or its delta is <= 0
(``stats.delta`` answers 0 for an absent family, so never divide by one
unchecked); when a span lacks an argument; when span_reduce.py fails or takes
over 120 s; when no prefill lies wholly inside the capture. run.py turns any
exception or SystemExit of a reader into exit code 1, which refuses the PR
(ledger, PR 25).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import span_reduce, stats

LOOP = "kukeon_engine_loop_seconds_total"
STEPS = "kukeon_engine_steps_total"
PREFILL_TOKENS = "kukeon_engine_prefill_tokens_total"
HOST_WORK = span_reduce.HOST_WORK
LIMIT_S = 120.0


def reduction(ctx: dict) -> dict | None:
    """span_reduce.py's reduction of the run's capture: run once a run, in a
    process of its own on the CPU backend, cached in ``ctx`` and beside the
    capture. None where there is no capture, the reduction failed, or it holds
    no span of the program's."""
    if "_spans" not in ctx:
        ctx["_spans"] = _reduce(ctx)
    return ctx["_spans"]


def _reduce(ctx: dict) -> dict | None:
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "span_reduction.json")
        if not os.path.exists(out):
            subprocess.run(
                [sys.executable, span_reduce.__file__, path, out], check=True,
                timeout=LIMIT_S, cwd=span_reduce.REPO,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            red = json.load(f)
        if not red.get("spans"):
            return None
        _show_token_counts(ctx, red)
        return red
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"spans: no reduction of this capture ({type(e).__name__}: {e})",
              flush=True)
        return None


def _show_token_counts(ctx: dict, red: dict) -> None:
    """The capture's prefill tokens by the counters beside the same by the
    spans' arguments: the two sources of one count, for a reader of the run."""
    cap = ctx["capture"]
    kinds = ("real", "padded", "cached")
    by_counter = {k: stats.delta(cap["metrics_before"], cap["metrics_after"],
                                 PREFILL_TOKENS, kind=k) for k in kinds}
    by_span = {k: sum(st.get(k, 0) for _s, _d, st
                      in red["events"]["engine.prefill_dispatch"])
               for k in kinds}
    print(f"prefill tokens over the capture: counters {json.dumps(by_counter)}"
          f", spans {json.dumps(by_span)}", flush=True)


def window_delta(ctx: dict, family: str, **labels) -> float:
    """A counter's growth between the window's two scrapes; 0 for a family
    the program does not export."""
    return stats.delta(ctx["metrics_open"], ctx["metrics_close"], family,
                       **labels)
