"""What the readers of the ``ssm_hybrid`` family's cells share.

Its decode program is a scan over steps whose body is ONE scan over the
periods of the layer pattern, each a scan over the mixers before the attention
layer, that layer, and a scan over the mixers after it
(``kukeon_tpu/models/ssm_hybrid.py``). An instruction of the longer run's body
runs ``periods x that run's mixers`` times a step, and nothing runs more often,
so the most-run instruction of each decode program over that number is its
steps (``_common.decode_steps`` divides by ``num_hidden_layers``, which holds
for one scan over equal layers).

The scan kernel's device time comes from a pass of its own over the capture
(``scan_calls``, in a process of its own on the CPU backend like
trace_reduce.py): device 0's events whose instruction is named after the
kernel (``selective_scan``: the ``name`` of its ``pallas_call``), each with the
time steps, channels and states of its call, read from the result shapes in
the event's own HLO line.

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
KERNEL = re.compile(r"^%?selective_scan[.\w-]*$")
# y [S, blocks, 8, 128] and the state [blocks, N, 8, 128], in this order
RESULT = re.compile(r"f32\[(\d+),(\d+),8,128\].*?f32\[\2,(\d+),8,128\]")
LIMIT_S = 120.0


def runs_a_step(cfg: dict) -> int:
    """How often the most-run instruction of the decode program runs a step."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    periods = cfg["num_hidden_layers"] // period
    return max(1, periods * max(offset, period - offset - 1))


def decode_steps(ctx: dict) -> float | None:
    most = 0
    for name, m in c.device0(ctx)["modules"].items():
        if "decode_chunk" in name:
            most += sum(m["max_op_count"].values())
    return most / runs_a_step(ctx["config"]) if most else None


def scan_calls(ctx: dict) -> list | None:
    """[[seconds, time steps, channels, states]] of the scan kernel's events
    on device 0, once a run (cached in ``ctx`` and beside the capture)."""
    if "_ssm_hybrid_scans" not in ctx:
        ctx["_ssm_hybrid_scans"] = _scan_calls(ctx)
    return ctx["_ssm_hybrid_scans"]


def _scan_calls(ctx: dict) -> list | None:
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "ssm_hybrid_scans.json")
        subprocess.run(
            [sys.executable, "-m", "benchmark.layer_metrics._ssm_hybrid",
             path, out], check=True, timeout=LIMIT_S, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            return json.load(f)
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"ssm_hybrid: no pass over this capture's operations "
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
            steps, blocks, states = (int(g) for g in shape.groups())
            out.append([d, steps, blocks * 1024, states])
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
        print(f"selective scan: {n:5d} calls of {steps:5d} time steps, "
              f"{s:9.5f} s, {s / n * 1e3:8.4f} ms a call", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(calls, f)
