"""Of the cache rows the decode chunks dispatched in the window had before
them (``kukeon_engine_decode_kv_rows_total{what="held"}``: steps x slots x
rows, over every layer of every kind), the share their attention fetched
(``what="read"``: whole blocks up to each active slot's last live row where
the decode kernel of ``ops/decode_attention.py`` runs, every held row where
the XLA body does). It says how often the block-skipping read engages: 100 on
a program whose attention reads every row, the block-rounded live share on
one that follows the lengths. None on a program without the counter."""

from benchmark.layer_metrics import _spans

ROWS = "kukeon_engine_decode_kv_rows_total"


def read(ctx):
    held = _spans.window_delta(ctx, ROWS, what="held")
    read_ = _spans.window_delta(ctx, ROWS, what="read")
    if held <= 0 or read_ < 0:
        return None
    return 100.0 * read_ / held
