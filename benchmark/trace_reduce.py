"""Reduction of a jax.profiler capture (.xplane.pb) to what the per-layer
metrics read: per device the traced window, the union of the intervals in
which an operation ran, time by jitted module and by operation, the time in
collectives, and the longest idle gaps.

    python benchmark/trace_reduce.py <capture dir or .xplane.pb> <out.json>
    python benchmark/trace_reduce.py --dump <capture dir or .xplane.pb>

Run in a process of its own on the CPU backend, after the cell has exited: it
needs jax.profiler.ProfileData and no device.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
MODULE_NAME = re.compile(r"^(.*?)\((\d+)\)$")
WEIGHT_OPERAND = re.compile(r"s8\[[0-9,]+\]")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit(f"trace_reduce: no .xplane.pb under {path}")
    return found[-1]


def read_planes(path: str) -> list[dict]:
    """[{name, lines: {line name: [(name, start_s, dur_s)]}}] of a capture."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def leaf_events(events: list[tuple]) -> list[tuple]:
    """Events that contain no other event of the line: a ``while`` or a
    ``call`` spans its body's operations, whose time would count twice."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    keep, stack = [], []
    for ev in ordered:
        end = ev[1] + ev[2]
        while stack and stack[-1][1] <= ev[1] + 5e-10:   # times are whole ns
            top = stack.pop()
            if not top[2]:
                keep.append(top[0])
        if stack:
            stack[-1][2] = True
        stack.append([ev, end, False])
    keep.extend(top[0] for top in stack if not top[2])
    return keep


def op_label(text: str) -> str:
    """A device operation's name as the trace gives it is the whole HLO line;
    keep the instruction's name, its result shape and the int8 operand (the
    weight matrix) that tells one fused matrix product from another."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:120]
    shape = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    weights = WEIGHT_OPERAND.findall(rest.split(" ", 1)[-1])
    label = f"{name.lstrip('%')} {shape}"
    return (label + (" <- " + weights[0] if weights else ""))[:120]


def per_program(mods: list[tuple], leaves: list[tuple]) -> dict:
    """{(module, program id): the largest number of times one instruction ran
    inside that program's traced events}. An instruction of a layer scan's
    body runs once per layer and step, so for a decode program this is layers
    x steps, whatever the compiler called the instruction."""
    counts: dict = {}
    ordered = sorted(leaves, key=lambda e: e[1])
    i = 0
    for n, s, d in mods:
        key = module_of(n)
        while i < len(ordered) and ordered[i][1] < s - 5e-10:
            i += 1
        j = i
        while j < len(ordered) and ordered[j][1] < s + d:
            c = counts.setdefault(key, {})
            short = ordered[j][0].partition(" = ")[0]
            c[short] = c.get(short, 0) + 1
            j += 1
        i = j
    return {key: max(c.values()) for key, c in counts.items() if c}


def module_of(name: str) -> tuple[str, str]:
    m = MODULE_NAME.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


def reduce_device(lines: dict, t_lo: float, t_hi: float) -> dict:
    ops = lines.get(OP_LINE, [])
    mods = sorted(lines.get(MODULE_LINE, []), key=lambda e: e[1])
    busy = union([(s, s + d) for _n, s, d in ops])
    leaves = leaf_events(ops)
    by_op: dict[str, float] = {}
    for n, _s, d in leaves:
        by_op[op_label(n)] = by_op.get(op_label(n), 0.0) + d
    modules: dict[str, dict] = {}
    for n, s, d in mods:
        name, program = module_of(n)
        m = modules.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "events": [], "max_op_count": {}})
        m["count"] += 1
        m["seconds"] += d
        m["events"].append([s - t_lo, d, program])
    for (name, program), most in per_program(mods, leaves).items():
        modules[name]["max_op_count"][program] = most

    def around(t: float) -> str:
        before = [module_of(n)[0] for n, s, d in mods if s + d <= t + 1e-9]
        after = [module_of(n)[0] for n, s, _d in mods if s >= t - 1e-9]
        return f"{before[-1] if before else 'start'}"  \
               f"->{after[0] if after else 'end'}"

    edges = [t_lo] + [x for ab in busy for x in ab] + [t_hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)), reverse=True)
    return {
        "window_s": t_hi - t_lo,
        "busy_s": sum(b - a for a, b in busy),
        "collective_s": sum(d for n, _s, d in leaves if COLLECTIVE.match(n)),
        "modules": modules,
        "top_ops": [[n, s] for n, s in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:40]],
        "idle_gaps": [[around(start + gap / 2), gap]
                      for gap, start in gaps[:10] if gap > 0],
    }


def reduce(path: str) -> dict:
    planes = read_planes(path)
    devices = sorted(((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                      for p in planes if DEVICE_PLANE.match(p["name"])),
                     key=lambda kv: kv[0])
    spans = [(s, s + d) for _i, p in devices
             for ev in p["lines"].values() for _n, s, d in ev]
    if not spans:
        raise SystemExit("trace_reduce: no operation ran on a device in "
                         f"this capture; planes {[p['name'] for p in planes]}")
    t_lo, t_hi = min(a for a, _b in spans), max(b for _a, b in spans)
    return {"planes": [p["name"] for p in planes],
            "devices": [{"device": i, **reduce_device(p["lines"], t_lo, t_hi)}
                        for i, p in devices]}


def dump(path: str) -> None:
    for p in read_planes(path):
        print("plane", p["name"])
        for name, events in p["lines"].items():
            total = sum(d for _n, _s, d in events)
            print(f"  line {name!r}: {len(events)} events, {total:.4f} s")
            seen: dict[str, list] = {}
            for n, _s, d in events:
                e = seen.setdefault(n, [0, 0.0])
                e[0] += 1
                e[1] += d
            for n, (c, t) in sorted(seen.items(), key=lambda kv: -kv[1][1])[:12]:
                print(f"    {t:10.5f} s x{c:<6} {n[:110]}")


if __name__ == "__main__":
    if sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        with open(sys.argv[2], "w") as f:
            json.dump(reduce(sys.argv[1]), f)
