"""Device time of the prefill modules (prefill and prefill_ext) per thousand
PADDED tokens they processed. The padded tokens are the engine's own count
(``kukeon_program_tokens_total{program="prefill"}``) between the scrapes around
the capture, scaled by traced modules over dispatches counted."""

from benchmark.layer_metrics import _common as c


def read(ctx):
    traced = c.modules(ctx, "prefill")
    tokens = c.capture_delta(ctx, "kukeon_program_tokens_total",
                             program="prefill")
    dispatched = (c.capture_delta(ctx, "kukeon_program_dispatch_total",
                                  program="prefill")
                  + c.capture_delta(ctx, "kukeon_program_dispatch_total",
                                    program="prefill_ext"))
    if not traced["count"] or tokens <= 0 or dispatched <= 0:
        return None
    padded = tokens * traced["count"] / dispatched
    return traced["seconds"] * 1e3 / (padded / 1e3)
