"""What the readers of the ``ssm_moe`` family's cells share.

Its decode program is a scan over steps whose body walks the layers unrolled
(``kukeon_tpu/models/ssm_moe.py``): every instruction of the body runs once a
step, so the most-run instruction of each decode program is its steps
(``_common.decode_steps`` divides by ``num_hidden_layers``, which holds for one
scan over equal layers).

The chunked scan's device time comes from a pass of its own over the capture
(``scan_calls``, in a process of its own on the CPU backend like
trace_reduce.py): device 0's events whose instruction is named after the
kernel (``ssd_scan``: the ``name`` of its ``pallas_call``), each with the time
steps, channels and states of its call, read from the result shapes in the
event's own HLO line (``y [S, I]`` in the activations' dtype and the state
``[N, I]`` in float32).

Every reader here returns None, and never raises, where the program has no
such module, span, counter or kernel (``_spans.py`` says why).
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
STATE_STEPS = "kukeon_engine_state_slot_steps_total"
TOKENS = "kukeon_moe_routed_tokens_total"
HITS = "kukeon_moe_held_hits_total"
KERNEL = re.compile(r"^%?ssd_scan[.\w-]*$")
# y [S, I] and the state [N, I], in this order
RESULT = re.compile(r"\w+\[(\d+),(\d+)\].*?f32\[(\d+),\2\]")
LIMIT_S = 120.0


def decode_steps(ctx: dict) -> float | None:
    most = 0
    for name, m in c.device0(ctx)["modules"].items():
        if "decode_chunk" in name:
            most += sum(m["max_op_count"].values())
    return float(most) if most else None


def scan_calls(ctx: dict) -> list | None:
    """[[seconds, time steps, channels, states]] of the scan kernel's events
    on device 0, once a run (cached in ``ctx`` and beside the capture)."""
    if "_ssm_moe_scans" not in ctx:
        ctx["_ssm_moe_scans"] = _scan_calls(ctx)
    return ctx["_ssm_moe_scans"]


def _scan_calls(ctx: dict) -> list | None:
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "ssm_moe_scans.json")
        subprocess.run(
            [sys.executable, "-m", "benchmark.layer_metrics._ssm_moe",
             path, out], check=True, timeout=LIMIT_S, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            return json.load(f)
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"ssm_moe: no pass over this capture's operations "
              f"({type(e).__name__}: {e})", flush=True)
        return None


def reduce_scans(lines: dict) -> list:
    """The kernel's events among a device plane's operations; ``lines`` as
    ``trace_reduce.read_planes`` gives a plane's."""
    from benchmark import trace_reduce as tr

    out = []
    for name, _s, d in tr.leaf_events(lines.get(tr.OP_LINE, [])):
        head, _, rest = name.partition(" = ")
        shape = RESULT.search(rest)
        if KERNEL.match(head.strip()) and shape:
            steps, channels, states = (int(g) for g in shape.groups())
            out.append([d, steps, channels, states])
    return out


if __name__ == "__main__":      # python -m ..., from the checkout's root
    from benchmark import trace_reduce as tr

    planes = [p for p in tr.read_planes(sys.argv[1])
              if tr.DEVICE_PLANE.match(p["name"])]
    first = min(planes, key=lambda p: int(
        tr.DEVICE_PLANE.match(p["name"]).group(1)))
    calls = reduce_scans(first["lines"])
    by_steps: dict = {}
    for d, steps, _channels, _states in calls:
        row = by_steps.setdefault(steps, [0, 0.0])
        row[0] += 1
        row[1] += d
    for steps, (n, s) in sorted(by_steps.items()):
        print(f"chunked scan: {n:5d} calls of {steps:5d} time steps, "
              f"{s:9.5f} s, {s / n * 1e3:8.4f} ms a call", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(calls, f)
