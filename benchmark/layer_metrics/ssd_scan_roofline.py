"""Share of its roofline the chunked state-space scan's kernel reached: the
least time its calls in the capture could take (opcount/ssd_scan.py at each
call's own time steps, channels and states, the heads' width and the chunk
from the configuration: matrix operations over the bf16 peak, or the bytes of
x, y, B, C and the state over HBM bandwidth, whichever is larger; the two are
close at the published sizes) over the device time of the kernel's events,
found by the kernel's name (``_ssm_moe.scan_calls``). Forming the decay
matrices is vector work the count leaves out, so a kernel bound by it reads a
low share. None where the capture holds no such event: a program without the
kernel, or no prefill inside the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _ssm_moe as s


def read(ctx):
    calls = s.scan_calls(ctx)
    if not calls:
        return None
    count = plugins.load("opcount", "ssd_scan", ctx["pkg_dir"]).count
    width = ctx["config"]["mamba_d_head"]
    chunk = ctx["config"]["mamba_chunk_size"]
    p = c.peaks(ctx)
    least = seconds = 0.0
    for d, steps, channels, states in calls:
        need = count(steps, channels // width, width, states, chunk)
        least += max(need["bytes"] / p["hbm_bytes_per_s"],
                     need["flops"] / p["bf16_flops_per_s"])
        seconds += d
    return 100.0 * least / seconds if seconds > 0 else None
