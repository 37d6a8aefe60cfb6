"""Mean time a request waited to be dequeued for a slot (submit -> admitted),
over every request admitted between the window's scrapes: the exact mean of
what ``queue_wait_p90_ms`` interpolates inside a power-of-two bucket."""

from benchmark.layer_metrics import _request_phases as rp


def read(ctx):
    return rp.mean_ms(ctx, rp.QUEUE_WAIT)
