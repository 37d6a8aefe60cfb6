"""90th percentile of the engine's queue wait (submit to slot) over the
window, from the buckets of ``kukeon_engine_queue_wait_seconds``."""

from benchmark import stats


def read(ctx):
    q = stats.histogram_quantile(ctx["metrics_open"], ctx["metrics_close"],
                                 "kukeon_engine_queue_wait_seconds", 90)
    return None if q is None else q * 1e3
