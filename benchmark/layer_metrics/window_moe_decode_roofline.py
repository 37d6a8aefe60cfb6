"""Share of its roofline the window_moe family's decode step reached: the
least time one step could take on this chip (the larger of bytes over HBM
bandwidth and operations over the bf16 peak; bytes bound it) over the device
time per step. Bytes and operations come from
opcount/window_moe_decode_chunk.py at the mean slots, ring rows and full rows
the client saw held during the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _window_moe as w


def read(ctx):
    steps = w.decode_steps(ctx)
    held = w.live(ctx)
    if not steps or held["slots"] <= 0:
        return None
    per_step = c.modules(ctx, "decode_chunk")["seconds"] / steps
    need = plugins.load("opcount", "window_moe_decode_chunk",
                        ctx["pkg_dir"]).count(
        ctx["config"], held["slots"], held["window_rows"], held["full_rows"])
    p = c.peaks(ctx)
    least = max(need["bytes"] / p["hbm_bytes_per_s"],
                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / per_step
