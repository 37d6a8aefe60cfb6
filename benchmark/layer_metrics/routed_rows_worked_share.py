"""Of the (token, choice) pairs the expert layers' calls made over the window
(``kukeon_moe_pair_rows_total``: tokens x top-k, once a call of the layer,
prefill pieces and decode steps alike), the share of sorted rows the three
ragged products and everything around them worked over
(``kukeon_moe_pair_rows_worked_total``: whole blocks, as many as the pairs
that chose a held expert of a counted token fill). The held share rounded up
to blocks where the products walk the front of the sorted pairs only; what a
program that works over every pair would read as 100 it does not say, because
such a program has no such counter: None on a program without the counters."""

from benchmark.layer_metrics import _spans

PAIRS = "kukeon_moe_pair_rows_total"
WORKED = "kukeon_moe_pair_rows_worked_total"


def read(ctx):
    pairs = _spans.window_delta(ctx, PAIRS)
    worked = _spans.window_delta(ctx, WORKED)
    if pairs <= 0 or worked < 0:
        return None
    return 100.0 * worked / pairs
