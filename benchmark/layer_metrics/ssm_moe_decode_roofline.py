"""Share of its roofline the ssm_moe family's decode step reached: the least
time one step could take on this chip (the larger of bytes over HBM bandwidth
and operations over the bf16 peak; bytes bound it) over the device time per
step. Bytes and operations come from opcount/ssm_moe_decode_chunk.py: the
weights outside the routed experts, the held experts a step's tokens reach at
even routing, the state of the ACTIVE slots read and written (the slots the
client saw held during the capture; an idle slot's state is not counted, the
step does not touch it), and their live KV rows."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _ssm_moe as s


def read(ctx):
    steps = s.decode_steps(ctx)
    live = ctx["live"]
    if not steps or live["slots"] <= 0:
        return None
    per_step = c.modules(ctx, "decode_chunk")["seconds"] / steps
    need = plugins.load("opcount", "ssm_moe_decode_chunk",
                        ctx["pkg_dir"]).count(
        ctx["config"], live["slots"], live["kv_rows"])
    p = c.peaks(ctx)
    least = max(need["bytes"] / p["hbm_bytes_per_s"],
                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / per_step
