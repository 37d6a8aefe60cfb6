"""Share of its roofline the expert layer's products reached inside the decode
step: the least time the routed and shared products and the router of every
expert layer could take a step (opcount/window_moe_decode_chunk.py
``expert_layer`` at the slots held during the capture: bytes bound it) over
the device time of the operations that read the expert stacks and the shared
expert, found by their operand shapes (``_window_moe.expert_ops``). None
where those shapes are not found in the capture, never a guess."""

from benchmark import plugins
from benchmark.layer_metrics import _common as c
from benchmark.layer_metrics import _window_moe as w


def read(ctx):
    steps = w.decode_steps(ctx)
    slots = w.live(ctx)["slots"]
    ops = w.expert_ops(ctx) if steps and slots > 0 else None
    if not ops or ops["routed_s"] <= 0 or ops["shared_s"] <= 0:
        return None
    opcount = plugins.load("opcount", "window_moe_decode_chunk",
                           ctx["pkg_dir"])
    s = opcount.shapes(ctx["config"])
    need = opcount.expert_layer(s, slots)
    p = c.peaks(ctx)
    least = s["n_expert"] * max(need["bytes"] / p["hbm_bytes_per_s"],
                                need["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least * steps / (ops["routed_s"] + ops["shared_s"])
