"""State moved over state held: of the slot states the program held over the
decode chunks dispatched in the window
(``kukeon_engine_state_slot_steps_total{what="held"}``: steps x every slot of
the program x the kinds that hold a state), the share that belonged to a slot
that was decoding (``what="active"``), which are the ones a step reads and
writes (4 MiB a mixer each, whatever the slot's length): the occupancy the
step's state traffic is paid for. None on a program without the counter."""

from benchmark.layer_metrics import _spans
from benchmark.layer_metrics import _ssm_moe as s


def read(ctx):
    held = _spans.window_delta(ctx, s.STATE_STEPS, what="held")
    active = _spans.window_delta(ctx, s.STATE_STEPS, what="active")
    if held <= 0 or active < 0:
        return None
    return 100.0 * active / held
