"""Share of its roofline the selective scan's kernel reached: the least time
its calls in the capture could take (opcount/selective_scan.py at each call's
own time steps, channels and states: the bytes bound it, the scan's
operations are vector work and the peak in peaks.json is the matrix unit's)
over the device time of the kernel's events, found by the kernel's name
(``_ssm_hybrid.scan_calls``). None where the capture holds no such event: a
program without the kernel, or no prefill inside the capture."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _ssm_hybrid as s


def read(ctx):
    calls = s.scan_calls(ctx)
    if not calls:
        return None
    count = plugins.load("opcount", "selective_scan", ctx["pkg_dir"]).count
    p = c.peaks(ctx)
    least = seconds = 0.0
    for d, steps, channels, states in calls:
        need = count(steps, channels, states)
        least += max(need["bytes"] / p["hbm_bytes_per_s"],
                     need["flops"] / p["bf16_flops_per_s"])
        seconds += d
    return 100.0 * least / seconds if seconds > 0 else None
