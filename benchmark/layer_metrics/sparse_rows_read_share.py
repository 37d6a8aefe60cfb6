"""Of the cache rows that were live before the decode steps of the window
(``kukeon_sparse_rows_live_total``: every active slot's rows and its own, a
layer and step), the share whose latent row the attention read
(``kukeon_sparse_rows_selected_total``: the min(index_topk, length + 1) it
selected): about index_topk over the mean length, and 100 on a program that
attends every live row. Both are summed on the device by the model's decode
step. None on a program without the counters."""

from benchmark.layer_metrics import _sparse_latent as s
from benchmark.layer_metrics import _spans


def read(ctx):
    live = _spans.window_delta(ctx, s.LIVE)
    selected = _spans.window_delta(ctx, s.SELECTED)
    if live <= 0 or selected < 0:
        return None
    return 100.0 * selected / live
