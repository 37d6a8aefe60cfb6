"""Device time of the ssm_hybrid family's decode module per decode step it ran
(``_ssm_hybrid.decode_steps``: the most-run instruction of each decode program
over the times it runs a step)."""

from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _ssm_hybrid as s


def read(ctx):
    steps = s.decode_steps(ctx)
    if not steps:
        return None
    return c.modules(ctx, "decode_chunk")["seconds"] * 1e3 / steps
