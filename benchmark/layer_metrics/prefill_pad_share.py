"""Share of the tokens the prefill programs ran in the window that were
padding: 1 - real / padded of ``kukeon_engine_prefill_tokens_total``."""

from benchmark.layer_metrics import _spans


def read(ctx):
    real = _spans.window_delta(ctx, _spans.PREFILL_TOKENS, kind="real")
    padded = _spans.window_delta(ctx, _spans.PREFILL_TOKENS, kind="padded")
    if real <= 0 or padded <= 0:
        return None
    return 100.0 * (1.0 - real / padded)
