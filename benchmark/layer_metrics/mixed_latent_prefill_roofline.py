"""Share of its roofline the two-kind latent decoder's WHOLE prefill modules
reached: the least time the captured prefills could take (operations over the
bf16 peak bound a long prompt: the full layers' index scores over every
earlier token and their attention over the SELECTED ones, the window layers'
over their windows) over their modules' device time. A prefill is one
``engine.prefill_dispatch`` span paired with the module event it launched
(``span_reduce.pair_prefills``): its real tokens are the span's ``real``. This
family has no prefix store: every prompt counts whole. None on a program
without the spans."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _spans


def read(ctx):
    red = _spans.reduction(ctx)
    pairs = [p for p in (red or {}).get("prefills", [])
             if p.get("real") and p.get("module_s")]
    if not pairs:
        return None
    count = plugins.load("opcount", "mixed_latent_prefill",
                         ctx["pkg_dir"]).count
    p = c.peaks(ctx)
    least = 0.0
    for pair in pairs:
        need = count(ctx["config"], pair["real"])
        least += max(need["flops"] / p["bf16_flops_per_s"],
                     need["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / sum(pair["module_s"] for pair in pairs)
