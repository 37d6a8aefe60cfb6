"""Device time of the ssm_moe family's decode module per decode step it ran
(``_ssm_moe.decode_steps``: the layers are unrolled in the step's body, so the
most-run instruction of each decode program is its steps)."""

from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _ssm_moe as s


def read(ctx):
    steps = s.decode_steps(ctx)
    if not steps:
        return None
    return c.modules(ctx, "decode_chunk")["seconds"] * 1e3 / steps
