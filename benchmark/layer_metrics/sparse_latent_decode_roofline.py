"""Share of its roofline the sparse_latent_moe family's decode step reached:
the least time one step could take on this chip (the larger of bytes over HBM
bandwidth and operations over the bf16 peak; bytes bound it) over the device
time per step. Bytes and operations come from
opcount/sparse_latent_decode_chunk.py: the weights, the held experts a step's
tokens hit, the index keys of the live rows and the latent rows of the
SELECTED ones, for the slots and rows the client saw held during the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _sparse_latent as s


def read(ctx):
    steps = s.decode_steps(ctx)
    live = ctx["live"]
    if not steps or live["slots"] <= 0:
        return None
    per_step = c.modules(ctx, "decode_chunk")["seconds"] / steps
    need = plugins.load("opcount", "sparse_latent_decode_chunk",
                        ctx["pkg_dir"]).count(
        ctx["config"], live["slots"], live["kv_rows"])
    p = c.peaks(ctx)
    least = max(need["bytes"] / p["hbm_bytes_per_s"],
                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / per_step
