"""Device time of the decode module per decode step it ran."""

from benchmark.layer_metrics import _common as c


def read(ctx):
    steps = c.decode_steps(ctx)
    if not steps:
        return None
    return c.modules(ctx, "decode_chunk")["seconds"] * 1e3 / steps
