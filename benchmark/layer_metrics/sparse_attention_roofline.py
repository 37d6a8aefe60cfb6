"""Share of their roofline the selecting attention's kernels reached: the
least time their calls in the capture could take over the device time of
their events, found by the kernels' names (``_sparse_latent.kernel_calls``):
the prefill's selection (index scores and the search for the topk-th, against
opcount/sparse_select_rows.py), the prefill's attention under the mask
(against opcount/sparse_masked_attention.py: the SELECTED pairs are what it
needs, so a kernel that runs every causal pair reads low) and the decode
step's index scores (opcount/sparse_decode_index_scores.py, at the live rows
the program counted over the capture). A chunk of a long prompt's queries is
counted at the mean of its prompt's chunks, which the capture holds alike. The
decode step's gather and its attention over the gathered rows are XLA's own
fusions, carry no name and are left to ``sparse_latent_decode_roofline``. None
where the capture holds no such event."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _sparse_latent as s


def read(ctx):
    calls = s.kernel_calls(ctx)
    if not calls:
        return None
    cfg, p = ctx["config"], c.peaks(ctx)
    load = lambda name: plugins.load("opcount", name, ctx["pkg_dir"])  # noqa: E731
    select, attend = load("sparse_select_rows"), load("sparse_masked_attention")
    scores = load("sparse_decode_index_scores")
    hi, di, topk = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["index_topk"])
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    decode = [x for x in calls if x[0] == "decode_index_scores"]
    # live index rows a call, from the program's own count over the capture
    live = (c.capture_delta(ctx, s.LIVE) / len(decode)) if decode else 0.0
    least = seconds = 0.0
    for kind, d, *sizes in calls:
        if kind == "select_rows":
            queries, keys = sizes
            need = select.count(queries, keys, queries * (keys + 1) / 2.0,
                                hi, di)
        elif kind == "masked_attention":
            heads, queries, keys, width = sizes
            chosen = attend.selected_pairs(0, keys, topk) * queries / keys
            need = attend.count(heads, queries, keys, chosen, dk, width)
        else:
            slots, rows = sizes
            need = scores.count(slots, min(live, slots * rows), hi, di)
        least += max(need["bytes"] / p["hbm_bytes_per_s"],
                     need["flops"] / p["bf16_flops_per_s"])
        seconds += d
    return 100.0 * least / seconds if seconds > 0 else None
