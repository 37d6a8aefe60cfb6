"""Choices of a held expert a routed token made, over the window: of the
(token, expert layer) pairs the routers saw (``kukeon_moe_routed_tokens_total``:
real prompt tokens and active slots), the hits on the experts this chip holds
(``kukeon_moe_held_hits_total``). Even routing gives top-k x held / router
width (0.5 for 8 x 16 / 256); it is the routed work a seed's weights give this
chip, and what the evened selection bias is judged by. None on a program
without the counters."""

from benchmark.layer_metrics import _sparse_latent as s
from benchmark.layer_metrics import _spans


def read(ctx):
    tokens = _spans.window_delta(ctx, s.TOKENS)
    hits = _spans.window_delta(ctx, s.HITS)
    if tokens <= 0 or hits < 0:
        return None
    return hits / tokens
