"""The engine's own time per token: the mean gap between consecutive emitted
tokens of one request, over every gap that ended between the window's scrapes.
Above the device's step by what stalls a decoding slot: other requests'
prefills, the chunk's boundary, the host. Prints the three phase means' sum
beside the client's mean latency."""

from benchmark.layer_metrics import _request_phases as rp


def read(ctx):
    rp.show_sum(ctx)
    return rp.mean_ms(ctx, rp.TOKEN_GAP)
