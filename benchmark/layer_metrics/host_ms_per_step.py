"""The engine loop's own work per step that did work: the seconds of
``kukeon_engine_loop_seconds_total`` in the phases where the host works
(admit, decode_dispatch, emit, other; not the fetches, where it waits for the
device, nor idle_wait) over ``kukeon_engine_steps_total``, in the window."""

from benchmark.layer_metrics import _spans


def read(ctx):
    steps = _spans.window_delta(ctx, _spans.STEPS)
    host = sum(_spans.window_delta(ctx, _spans.LOOP, phase=p)
               for p in _spans.HOST_WORK)
    if steps <= 0 or host <= 0:
        return None
    return host * 1e3 / steps
