"""``held_experts_reached_share`` in the two-kind latent decoder's cell: of
the experts the expert layers' calls held over the window
(``kukeon_moe_held_experts_total``), the share whose group had a row
(``kukeon_moe_held_experts_reached_total``). The accepted reader lists its
cells, and a list that is there gains none: the same reading under a name of
this cell's own. None on a program without the counters."""

from benchmark.layer_metrics.held_experts_reached_share import read  # noqa: F401
