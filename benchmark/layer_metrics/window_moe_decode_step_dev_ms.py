"""Device time of the window_moe family's decode module per decode step it ran
(``_window_moe.decode_steps``: the most-run instruction of each decode program
over its whole periods)."""

from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _spans
from benchmark.layer_metrics import _window_moe as w


def read(ctx):
    # No metric of this cell reads the engine loop's spans yet (PERF.md
    # section 7): ask for their reduction here, for the span table and the
    # idle time by loop phase it prints into the traced run's output.
    _spans.reduction(ctx)
    steps = w.decode_steps(ctx)
    if not steps:
        return None
    return c.modules(ctx, "decode_chunk")["seconds"] * 1e3 / steps
