"""Of the tokens the active slots held before the decode steps of the window
(``kukeon_window_latent_rows_held_total``: every active slot's tokens and its
own, a window layer and step: what a full stack would have had the step
attend), the share the window layers attended
(``kukeon_window_latent_rows_read_total``: min(sliding_window_size, length +
1) a slot): about the window over the mean length, and 100 on prompts shorter
than the window. Both are summed on the device by the model's decode step.
None on a program without the counters."""

from benchmark.layer_metrics import _mixed_latent as m
from benchmark.layer_metrics import _spans


def read(ctx):
    held = _spans.window_delta(ctx, m.HELD)
    read_ = _spans.window_delta(ctx, m.READ)
    if held <= 0 or read_ < 0:
        return None
    return 100.0 * read_ / held
