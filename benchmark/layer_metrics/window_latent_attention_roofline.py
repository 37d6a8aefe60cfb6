"""Share of its roofline the window layers' decode attention reached: the
least time its calls in the capture could take over the device time of their
events, found by the kernel's name (``_mixed_latent.kernel_calls``). A call
needs the ring rows inside the active slots' windows and their own
(opcount/window_latent_decode_attention.py), at the rows the program counted
over the capture (``kukeon_window_latent_rows_read_total``, a call's share of
it); bytes bound it. A kernel that walks a slot's blocks one after another
with its first copy uncovered reads low. None where the capture holds no such
event or the program has no such counter."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _mixed_latent as m


def read(ctx):
    calls = m.kernel_calls(ctx)
    attended = c.capture_delta(ctx, m.READ)
    if not calls or attended <= 0:
        return None
    cfg, p = ctx["config"], c.peaks(ctx)
    count = plugins.load("opcount", "window_latent_decode_attention",
                         ctx["pkg_dir"]).count
    width = cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    # of the rows counted, a call's; a slot's own row among them
    rows = attended / len(calls)
    slots = min(ctx["live"]["slots"], rows)
    least = seconds = 0.0
    for d, _slots, heads, value in calls:
        need = count(slots, rows - slots, heads, width, value)
        least += max(need["bytes"] / p["hbm_bytes_per_s"],
                     need["flops"] / p["bf16_flops_per_s"])
        seconds += d
    return 100.0 * least / seconds if seconds > 0 else None
