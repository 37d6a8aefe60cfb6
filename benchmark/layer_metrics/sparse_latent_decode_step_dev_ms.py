"""Device time of the sparse_latent_moe family's decode module per decode step
it ran (``_sparse_latent.decode_steps``: the layers are unrolled in the step's
body, so the most-run instruction of each decode program is its steps)."""

from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _sparse_latent as s


def read(ctx):
    steps = s.decode_steps(ctx)
    if not steps:
        return None
    return c.modules(ctx, "decode_chunk")["seconds"] * 1e3 / steps
