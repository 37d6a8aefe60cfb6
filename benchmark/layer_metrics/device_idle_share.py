"""1 - the union of the intervals in which an operation ran on device 0, over
the traced window."""

from benchmark.layer_metrics import _common as c


def read(ctx):
    d = c.device0(ctx)
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"]) if d["window_s"] else None
