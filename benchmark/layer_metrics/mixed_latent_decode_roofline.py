"""Share of its roofline the two-kind latent decoder's WHOLE decode step
reached: the least time one step could take on this chip (the larger of bytes
over HBM bandwidth and operations over the bf16 peak; bytes bound it) over the
device time per step. Bytes and operations come from
opcount/mixed_latent_decode_chunk.py, which counts what a step NEEDS: the
weights, the held experts its tokens reach, of the full layers the index keys
of the live rows and the latent rows of the SELECTED ones, of the window
layers the ring rows inside the window, for the slots and rows the client saw
held during the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _mixed_latent as m


def read(ctx):
    steps = m.decode_steps(ctx)
    live = ctx["live"]
    if not steps or live["slots"] <= 0:
        return None
    per_step = c.modules(ctx, "decode_chunk")["seconds"] / steps
    need = plugins.load("opcount", "mixed_latent_decode_chunk",
                        ctx["pkg_dir"]).count(
        ctx["config"], live["slots"], live["kv_rows"])
    p = c.peaks(ctx)
    least = max(need["bytes"] / p["hbm_bytes_per_s"],
                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / per_step
