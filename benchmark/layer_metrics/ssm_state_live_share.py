"""Of the slot states the decode chunks dispatched in the window read and
wrote (``kukeon_engine_state_slot_steps_total{what="held"}``: steps x every
slot of the program x the kinds that hold a state), the share that belonged to
a slot that was decoding (``what="active"``). A state has no rows: a slot
costs a step the same bytes at any length, and an empty one as much as a full
one, so this is the occupancy the step's state traffic is paid for. None on a
program without the counter."""

from benchmark.layer_metrics import _ssm_hybrid as s
from benchmark.layer_metrics import _spans


def read(ctx):
    held = _spans.window_delta(ctx, s.STATE_STEPS, what="held")
    active = _spans.window_delta(ctx, s.STATE_STEPS, what="active")
    if held <= 0 or active < 0:
        return None
    return 100.0 * active / held
