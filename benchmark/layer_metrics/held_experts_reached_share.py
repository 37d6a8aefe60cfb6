"""Of the experts the expert layers' calls held over the window
(``kukeon_moe_held_experts_total``: the held count, once a call of the layer,
prefill pieces and decode steps alike), the share whose group had a row
(``kukeon_moe_held_experts_reached_total``): the share of the held stacks the
three ragged products of those calls had to read. 100 on a step that routes
every slot's rows, idle or not, at enough slots; where only the rows that
count are routed, what the active slots' and real tokens' choices reach. None
on a program without the counters."""

from benchmark.layer_metrics import _spans

HELD = "kukeon_moe_held_experts_total"
REACHED = "kukeon_moe_held_experts_reached_total"


def read(ctx):
    held = _spans.window_delta(ctx, HELD)
    reached = _spans.window_delta(ctx, REACHED)
    if held <= 0 or reached < 0:
        return None
    return 100.0 * reached / held
