"""Share of the window's prompt TOKENS that came from the prefix store and
not through the model: cached / (cached + real) of
``kukeon_engine_prefill_tokens_total`` (``prefix_hit_share`` counts
requests)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    cached = _spans.window_delta(ctx, _spans.PREFILL_TOKENS, kind="cached")
    real = _spans.window_delta(ctx, _spans.PREFILL_TOKENS, kind="real")
    if real <= 0 or cached < 0:
        return None
    return 100.0 * cached / (cached + real)
