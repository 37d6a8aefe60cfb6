"""Of the choices the routers made in the window (token x expert layer x
top-k, ``kukeon_moe_routed_total``), the share that chose an expert this chip
holds (``kukeon_moe_held_hits_total``): 100 x held / router width at even
routing (12.5 for 32 of 256), and the share of the routed work an
expert-parallel deployment would leave on this chip."""

from benchmark.layer_metrics import _spans
from benchmark.layer_metrics import _window_moe as w


def read(ctx):
    routed = _spans.window_delta(ctx, w.ROUTED)
    hits = _spans.window_delta(ctx, w.HITS)
    if routed <= 0:
        return None
    return 100.0 * hits / routed
