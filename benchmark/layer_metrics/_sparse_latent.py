"""What the readers of the ``sparse_latent_moe`` family's cells share.

Its decode program is a scan over steps whose body walks the layers unrolled
(``kukeon_tpu/models/sparse_latent_moe.py``): every instruction of the body
runs once a step, so the most-run instruction of each decode program is its
steps (``_common.decode_steps`` divides by ``num_hidden_layers``, which holds
for one scan over equal layers).

The device time of the selecting attention's kernels comes from a pass of its
own over the capture (``kernel_calls``, in a process of its own on the CPU
backend like trace_reduce.py): device 0's events whose instruction is named
after a kernel of ``ops/sparse_attention.py`` (the ``name`` of its
``pallas_call``), each with the sizes of its call, read from the shapes in the
event's own HLO line.

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
TOKENS = "kukeon_moe_routed_tokens_total"
HITS = "kukeon_moe_held_hits_total"
SELECTED = "kukeon_sparse_rows_selected_total"
LIVE = "kukeon_sparse_rows_live_total"
KERNEL = re.compile(
    r"^%?sparse_(select_rows|masked_attention|decode_index_scores)[.\w-]*$")
MASK = re.compile(r"s8\[(\d+),(\d+),(\d+)\]")           # key tiles, queries, tile
OUT = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")          # heads, queries, width
SCORES = re.compile(r"f32\[(\d+),1,(\d+)\]")            # slots, rows
LIMIT_S = 120.0


def decode_steps(ctx: dict) -> float | None:
    most = 0
    for name, m in c.device0(ctx)["modules"].items():
        if "decode_chunk" in name:
            most += sum(m["max_op_count"].values())
    return float(most) if most else None


def kernel_calls(ctx: dict) -> list | None:
    """[[kernel, seconds, sizes...]] of the kernels' events on device 0, once
    a run (cached in ``ctx`` and beside the capture): ``select_rows`` with its
    queries and keys, ``masked_attention`` with its heads, queries, keys and
    value width, ``decode_index_scores`` with its slots and rows."""
    if "_sparse_latent_calls" not in ctx:
        ctx["_sparse_latent_calls"] = _kernel_calls(ctx)
    return ctx["_sparse_latent_calls"]


def _kernel_calls(ctx: dict) -> list | None:
    try:
        path = ctx["capture"]["rec"]["path"]
        out = os.path.join(path, "sparse_latent_calls.json")
        subprocess.run(
            [sys.executable, "-m", "benchmark.layer_metrics._sparse_latent",
             path, out], check=True, timeout=LIMIT_S, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(out) as f:
            return json.load(f)
    except (KeyError, TypeError, ValueError, OSError,
            subprocess.SubprocessError) as e:
        print(f"sparse_latent: no pass over this capture's operations "
              f"({type(e).__name__}: {e})", flush=True)
        return None


def reduce_calls(lines: dict) -> list:
    """The kernels' events among a device plane's operations; ``lines`` as
    ``trace_reduce.read_planes`` gives a plane's."""
    from benchmark import trace_reduce as tr

    out = []
    for name, _s, d in tr.leaf_events(lines.get(tr.OP_LINE, [])):
        head, _, rest = name.partition(" = ")
        kernel = KERNEL.match(head.strip())
        if not kernel:
            continue
        kind = kernel.group(1)
        mask = MASK.search(rest)
        if kind == "select_rows" and mask:
            tiles, queries, tile = (int(g) for g in mask.groups())
            out.append([kind, d, queries, tiles * tile])
        elif kind == "masked_attention" and mask and OUT.search(rest):
            tiles, queries, tile = (int(g) for g in mask.groups())
            heads, _q, width = (int(g) for g in OUT.search(rest).groups())
            out.append([kind, d, heads, queries, tiles * tile, width])
        elif kind == "decode_index_scores" and SCORES.search(rest):
            slots, rows = (int(g) for g in SCORES.search(rest).groups())
            out.append([kind, d, slots, rows])
    return out


if __name__ == "__main__":      # python -m ..., from the checkout's root
    from benchmark import trace_reduce as tr

    planes = [p for p in tr.read_planes(sys.argv[1])
              if tr.DEVICE_PLANE.match(p["name"])]
    first = min(planes, key=lambda p: int(
        tr.DEVICE_PLANE.match(p["name"]).group(1)))
    calls = reduce_calls(first["lines"])
    by_kind: dict = {}
    for kind, d, *sizes in calls:
        row = by_kind.setdefault((kind, tuple(sizes)), [0, 0.0])
        row[0] += 1
        row[1] += d
    for (kind, sizes), (n, s) in sorted(by_kind.items()):
        print(f"sparse attention: {n:5d} calls of {kind} {list(sizes)}, "
              f"{s:9.5f} s, {s / n * 1e3:8.4f} ms a call", flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(calls, f)
