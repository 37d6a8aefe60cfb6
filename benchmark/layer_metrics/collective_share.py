"""Time device 0 spent in all-reduce, all-gather, reduce-scatter, all-to-all
or collective-permute operations over the capture."""

from benchmark.layer_metrics import _common as c


def read(ctx):
    d = c.device0(ctx)
    return 100.0 * d["collective_s"] / d["window_s"] if d["window_s"] else None
