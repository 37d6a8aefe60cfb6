"""Share of its roofline the prefill modules reached: the least time the real
prompt tokens prefilled during the capture could take (operations over the
bf16 peak bound it) over the modules' device time. Real tokens are the
client's: a follow-up turn under a prefixId counts its new tail only, as if
its prefix was found, so a miss lowers the share and never raises it."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c


def read(ctx):
    traced = c.modules(ctx, "prefill")
    seen = c.prefills_in_capture(ctx)
    if not traced["count"] or not seen:
        return None
    count = plugins.load("opcount", "prefill", ctx["pkg_dir"]).count
    p = c.peaks(ctx)
    chips = ctx["config"]["serving"]["chips"]
    least = 0.0
    for r in seen:
        need = count(ctx["config"], r["new_tokens"],
                     r["prompt_len"] - r["new_tokens"], chips=chips)
        least += max(need["flops"] / p["bf16_flops_per_s"],
                     need["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least * (traced["count"] / len(seen)) / traced["seconds"]
