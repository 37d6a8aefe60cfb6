"""Share of its roofline the decode step reached: the least time one step
could take on these chips (the larger of bytes over HBM bandwidth and
operations over the bf16 peak; bytes bound it) over the device time per step.
Bytes and operations come from opcount/decode_chunk.py at the mean live slots
and live KV rows the client saw during the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c


def read(ctx):
    steps = c.decode_steps(ctx)
    if not steps or ctx["live"]["slots"] <= 0:
        return None
    per_step = c.modules(ctx, "decode_chunk")["seconds"] / steps
    need = plugins.load("opcount", "decode_chunk", ctx["pkg_dir"]).count(
        ctx["config"], ctx["live"]["slots"], ctx["live"]["kv_rows"],
        chips=ctx["config"]["serving"]["chips"])
    p = c.peaks(ctx)
    least = max(need["bytes"] / p["hbm_bytes_per_s"],
                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / per_step
