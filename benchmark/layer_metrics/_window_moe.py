"""What the readers of the ``window_moe`` family's cells share.

Its decode program is not one scan over equal layers (``_common.decode_steps``
divides by ``num_hidden_layers``): a chunk is a scan over steps whose body is
the unrolled leading layers and one scan over the periods of expert layers. An
instruction of the step body runs once a step, one of the period scan's body
once a period and step, so the most-run instruction of each decode program
over the whole periods is its steps (one period: the compiler unrolls that
scan and both are the same).

A slot's rows differ by kind: a window layer's ring holds min(length, window)
rows, a full layer's every row. ``live`` is ``stats.mean_live`` with both.

The device time of the expert layer's products comes from a pass of its own
over the capture (``expert_ops``, in a process of its own on the CPU backend
like trace_reduce.py): the leaf operations inside the decode modules whose
HLO line names an operand of the expert stacks' shape ([held, H, Im] or
[held, Im, H]: the routed products, ``ragged-dot`` custom calls) or of the
shared expert's ([H, Im], [Im, H]). Where those shapes are also another
matrix's (Im equal to the query width, say) there is nothing to tell them
apart by, and the reader returns None.

Every reader here returns None, and never raises, where the program has no
such module, span or counter (``_spans.py`` says why).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

from benchmark.layer_metrics import _common as c

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROUTED = "kukeon_moe_routed_total"
HITS = "kukeon_moe_held_hits_total"
LIMIT_S = 120.0


def periods(cfg: dict) -> int:
    experts = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return max(1, experts // cfg["global_attn_every_n_layers"])


def decode_steps(ctx: dict) -> float | None:
    most = 0
    for name, m in c.device0(ctx)["modules"].items():
        if "decode_chunk" in name:
            most += sum(m["max_op_count"].values())
    return most / periods(ctx["config"]) if most else None


def live(ctx: dict) -> dict:
    """Mean over the capture of the slots held and of the rows they hold in a
    window layer and in a full layer (the client's clock and counts)."""
    t0 = ctx["capture"]["requested"]
    t1 = t0 + ctx["capture"]["duration_s"]
    window = ctx["config"]["sliding_window"]
    span = max(t1 - t0, 1e-9)
    out = {"slots": 0.0, "window_rows": 0.0, "full_rows": 0.0}
    for r in ctx["records"]:
        times = r.get("token_times") or []
        if len(times) < 2:
            continue
        a, b = max(times[0], t0), min(times[-1], t1)
        if b <= a:
            continue
        inside = [t for t in times if a <= t <= b]
        rows = r["prompt_len"] + (times.index(inside[0]) + len(inside) / 2.0
                                  if inside else len(times) / 2.0)
        share = (b - a) / span
        out["slots"] += share
        out["full_rows"] += rows * share
        out["window_rows"] += min(rows, window) * share
    return out


def stack_patterns(cfg: dict) -> dict | None:
    """Regular expressions of the operand shapes that name the routed and the
    shared products in an HLO line, or None where another matrix of the model
    has the same shape."""
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    d = cfg["head_dim"]
    others = {cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d,
              cfg["intermediate_size"], cfg["vocab_size"],
              cfg["router_experts"]}
    if im in others or (im != h and h in others):
        return None
    held = cfg["experts_held"][1]
    pair = f"(?:{h},{im}|{im},{h})"
    return {"routed": rf"bf16\[(?:\d+,)?{held},{pair}\]",
            "shared": rf"bf16\[(?:1,)?{pair}\]"}


def expert_ops(ctx: dict) -> dict | None:
    """{"routed_s", "shared_s", "ops"} inside the capture's decode modules,
    once a run (cached in ``ctx`` and beside the capture)."""
    if "_window_moe_ops" not in ctx:
        ctx["_window_moe_ops"] = _expert_ops(ctx)
    return ctx["_window_moe_ops"]


def _expert_ops(ctx: dict) -> dict | None:
    patterns = stack_patterns(ctx["config"])
    if patterns is None:
        return None
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "window_moe_ops.json")
        subprocess.run(
            [sys.executable, "-m", "benchmark.layer_metrics._window_moe",
             path, out, json.dumps(patterns)], check=True, timeout=LIMIT_S,
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            return json.load(f)
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"window_moe: no pass over this capture's operations "
              f"({type(e).__name__}: {e})", flush=True)
        return None


def reduce_ops(lines: dict, patterns: dict) -> dict:
    """Seconds of device 0's leaf operations inside its decode modules, by
    class; ``lines`` as ``trace_reduce.read_planes`` gives a plane's."""
    from benchmark import trace_reduce as tr

    routed = re.compile(patterns["routed"])
    shared = re.compile(patterns["shared"])
    mods = [(s, s + d) for n, s, d in lines.get(tr.MODULE_LINE, [])
            if "decode_chunk" in n]
    out = {"routed_s": 0.0, "shared_s": 0.0, "ops": {}}
    for name, s, d in tr.leaf_events(lines.get(tr.OP_LINE, [])):
        if not any(a - 5e-10 <= s < b for a, b in mods):
            continue
        operands = name.partition(" = ")[2]
        kind = ("routed" if "ragged-dot" in name.partition(" = ")[0]
                or routed.search(operands) else
                "shared" if shared.search(operands) else None)
        if kind:
            out[kind + "_s"] += d
            label = tr.op_label(name)
            out["ops"][label] = out["ops"].get(label, 0.0) + d
    return out


if __name__ == "__main__":      # python -m ..., from the checkout's root
    from benchmark import trace_reduce as tr

    planes = [p for p in tr.read_planes(sys.argv[1])
              if tr.DEVICE_PLANE.match(p["name"])]
    first = min(planes, key=lambda p: int(
        tr.DEVICE_PLANE.match(p["name"]).group(1)))
    reduced = reduce_ops(first["lines"], json.loads(sys.argv[3]))
    for label, s in sorted(reduced["ops"].items(), key=lambda kv: -kv[1]):
        print(f"expert layer: {s:9.5f} s  {label}", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(reduced, f)
