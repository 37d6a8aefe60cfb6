"""Mean time from leaving the queue to the first token emitted: the prefill,
the device work queued ahead of it, and the chunk its first token waits
behind. The engine's mean submit -> first token less its mean submit ->
admitted, each over what its histogram observed between the window's scrapes
(the two count the same requests but for those in flight at a scrape)."""

from benchmark.layer_metrics import _request_phases as rp


def read(ctx):
    first, queued = rp.mean_ms(ctx, rp.TTFT), rp.mean_ms(ctx, rp.QUEUE_WAIT)
    if first is None or queued is None or first < queued:
        return None
    return first - queued
