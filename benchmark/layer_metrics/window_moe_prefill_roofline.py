"""Share of its roofline the window_moe family's prefill modules reached: the
least time the real prompt tokens prefilled during the capture could take
(operations over the bf16 peak bound a long prompt, the weights' bytes a short
one) over the modules' device time. This family has no prefix store: every
prompt counts whole."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c


def read(ctx):
    traced = c.modules(ctx, "prefill")
    seen = c.prefills_in_capture(ctx)
    if not traced["count"] or not seen:
        return None
    count = plugins.load("opcount", "window_moe_prefill", ctx["pkg_dir"]).count
    p = c.peaks(ctx)
    least = 0.0
    for r in seen:
        need = count(ctx["config"], r["prompt_len"])
        least += max(need["flops"] / p["bf16_flops_per_s"],
                     need["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least * (traced["count"] / len(seen)) / traced["seconds"]
