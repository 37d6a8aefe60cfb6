"""Of device 0's idle time in the capture, the share in which the engine loop
was at work of its own (admit, decode_dispatch, emit, other): the host made the
device wait, as against no request to serve (idle_wait) and the fetches."""

from benchmark.layer_metrics import _spans


def read(ctx):
    red = _spans.reduction(ctx)
    if red is None or not red.get("idle_s"):
        return None
    by = red.get("idle_by_phase", {})
    return 100.0 * sum(by.get(p, 0.0) for p in _spans.HOST_WORK) / red["idle_s"]
