"""What the readers of the two-kind latent decoder's cell share (the
``mixed_latent_moe`` launcher: window layers with a latent attention of their
own among selecting latent layers).

Its decode program is a scan over steps whose body walks the layers unrolled
(``kukeon_tpu/models/sparse_latent_moe.py``), so the most-run instruction of
each decode program is its steps (``_sparse_latent.decode_steps``).

The device time of the window layers' decode kernel comes from a pass of its
own over the capture (``kernel_calls``, in a process of its own on the CPU
backend like trace_reduce.py): device 0's events whose instruction is named
after the kernel (the ``name`` of its ``pallas_call``), each with the slots,
heads and value width of its call, read from the result's shape in the event's
own HLO line.

Every reader here returns None, and never raises, where the program has no
such module, span, counter or kernel (``_spans.py`` says why).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

from benchmark.layer_metrics._sparse_latent import decode_steps  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READ = "kukeon_window_latent_rows_read_total"
HELD = "kukeon_window_latent_rows_held_total"
KERNEL = re.compile(r"^%?window_latent_decode_attention[.\w-]*$")
OUT = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")          # slots, heads, value
LIMIT_S = 120.0


def window_layers(cfg: dict) -> int:
    return sum(t == "sliding_attention" for t in cfg["layer_types"])


def kernel_calls(ctx: dict) -> list | None:
    """[[seconds, slots, heads, value width]] of the kernel's events on device
    0, once a run (cached in ``ctx`` and beside the capture)."""
    if "_mixed_latent_calls" not in ctx:
        ctx["_mixed_latent_calls"] = _kernel_calls(ctx)
    return ctx["_mixed_latent_calls"]


def _kernel_calls(ctx: dict) -> list | None:
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "mixed_latent_calls.json")
        subprocess.run(
            [sys.executable, "-m", "benchmark.layer_metrics._mixed_latent",
             path, out], check=True, timeout=LIMIT_S, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            return json.load(f)
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"mixed_latent: no pass over this capture's operations "
              f"({type(e).__name__}: {e})", flush=True)
        return None


def reduce_calls(lines: dict) -> list:
    """The kernel's events among a device plane's operations; ``lines`` as
    ``trace_reduce.read_planes`` gives a plane's."""
    from benchmark import trace_reduce as tr

    out = []
    for name, _s, d in tr.leaf_events(lines.get(tr.OP_LINE, [])):
        head, _, rest = name.partition(" = ")
        shape = OUT.search(rest)
        if KERNEL.match(head.strip()) and shape:
            out.append([d, *(int(g) for g in shape.groups())])
    return out


if __name__ == "__main__":      # python -m ..., from the checkout's root
    from benchmark import trace_reduce as tr

    planes = [p for p in tr.read_planes(sys.argv[1])
              if tr.DEVICE_PLANE.match(p["name"])]
    first = min(planes, key=lambda p: int(
        tr.DEVICE_PLANE.match(p["name"]).group(1)))
    calls = reduce_calls(first["lines"])
    if calls:
        s = sum(x[0] for x in calls)
        print(f"window latent attention: {len(calls):5d} calls of "
              f"{calls[0][1:]}, {s:9.5f} s, {s / len(calls) * 1e3:8.4f} ms a "
              "call", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(calls, f)
