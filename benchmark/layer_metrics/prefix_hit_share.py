"""Hits over lookups of the prefix cache in the window, among requests that
carry a prefixId (``kukeon_engine_prefix_cache_total``)."""

from benchmark import stats

FAMILY = "kukeon_engine_prefix_cache_total"


def read(ctx):
    a, b = ctx["metrics_open"], ctx["metrics_close"]
    hit = stats.delta(a, b, FAMILY, result="hit")
    miss = stats.delta(a, b, FAMILY, result="miss")
    return None if hit + miss <= 0 else 100.0 * hit / (hit + miss)
