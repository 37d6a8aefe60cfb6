"""Choices of a held expert a routed token made, over the window: of the
(token, layer) pairs the routers saw (``kukeon_moe_routed_tokens_total``: real
prompt tokens and active slots, in every layer), the hits on the experts this
chip holds (``kukeon_moe_held_hits_total``). Even routing gives top-k x held /
router width: 5.0 for 10 x 36 / 72; it is the routed work a seed's weights
give this chip. None on a program without the counters."""

from benchmark.layer_metrics import _spans
from benchmark.layer_metrics import _ssm_moe as s


def read(ctx):
    tokens = _spans.window_delta(ctx, s.TOKENS)
    hits = _spans.window_delta(ctx, s.HITS)
    if tokens <= 0 or hits < 0:
        return None
    return hits / tokens
