"""90th percentile of a request's mean gap between tokens at the client over the traced run's
window (nearest rank over every request sent). A tail over the 80-130 requests
a window holds spreads by 10-12% from run to run, so it stands here, without a
bound, beside the mean that is held to one."""


def read(ctx):
    return ctx["client"]["tpot_p90_ms"]
