"""Reduction of a capture's HOST side: the engine loop's own spans
(kukeon_tpu/obs/spans.py: ``engine.*`` and ``cell.*`` events on ``/host:CPU``,
their arguments as stats) against device 0's operations on the same clock.

    python benchmark/span_reduce.py <capture dir or .xplane.pb> <out.json>

Prints a table of span name / count / seconds and of device-idle seconds by the
phase the engine loop was in, and writes what the span readers of
``layer_metrics/`` need. Run in a process of its own on the CPU backend, like
trace_reduce.py. A capture of a program without the spans (the parent of the PR
that added them) reduces to ``{"spans": {}}``: the readers then return None.
Such a capture holds the Python tracer's frames, hundreds of thousands of
events on the same plane, so events are filtered by name while they are
iterated and no list of the others is built.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

HOST_PLANE = "/host:CPU"
# The spans that are phases of the engine loop's thread (obs/spans.py), which
# this file cannot import: it also runs over commits that lack it.
# engine.step outside its children is phase ``other``.
PHASE_OF = {"engine.step": "other", "engine.idle_wait": "idle_wait",
            **{"engine." + p: p for p in (
                "admit", "decode_dispatch", "fetch_first", "fetch_chunk",
                "emit")}}
# Phases in which the device waits for the HOST's work, as against waiting for
# a request (idle_wait) or the host waiting for the device (the fetches).
HOST_WORK = ("admit", "decode_dispatch", "emit", "other")
# Spans whose events the readers pair with device events, kept whole.
KEPT = ("engine.prefill_dispatch", "engine.first_token", "engine.fetch_chunk")


def read_capture(path: str) -> dict:
    """{"host": [(name, start_s, dur_s, stats)] of engine.* / cell.* events,
    "ops": [(start_s, dur_s)], "modules": [(name, start_s, dur_s)] of device
    0, "window": (lo, hi) over every device plane}. A device operation's
    event carries its time and no name stack (stats: device_offset_ps,
    device_duration_ps), so the named scopes of models/llama.py are not read
    here: PERF.md section 7."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(tr.find_xplane(path))
    host, ops, modules = [], [], []
    lo, hi = float("inf"), float("-inf")
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(("engine.", "cell.")):
                        host.append((name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9, dict(ev.stats)))
            continue
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        first = int(m.group(1)) == 0
        for line in plane.lines:
            keep = (ops if line.name == tr.OP_LINE else
                    modules if line.name == tr.MODULE_LINE else None)
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                lo, hi = min(lo, s), max(hi, s + d)
                if not first or keep is None:
                    continue
                keep.append((s, d) if keep is ops else (ev.name, s, d))
    return {"host": host, "ops": ops, "modules": modules,
            "window": (lo, hi) if lo < hi else None}


def phase_segments(host: list[tuple]) -> list[tuple[float, float, str]]:
    """(start, end, phase) of the engine thread's time, innermost phase
    winning: engine.step outside its children is ``other``."""
    spans = sorted(((s, s + d, PHASE_OF[n]) for n, s, d, _st in host
                    if n in PHASE_OF), key=lambda e: (e[0], -e[1]))
    out: list[tuple[float, float, str]] = []
    stack: list[list] = []          # [end, phase, covered up to]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, phase, at = stack.pop()
            if end > at:
                out.append((at, end, phase))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for start, end, phase in spans:
        close(start)
        if stack:
            if start > stack[-1][2]:
                out.append((stack[-1][2], start, stack[-1][1]))
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([end, phase, start])
    close(float("inf"))
    return sorted(out)


def overlap(gaps: list[tuple[float, float]],
            segments: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) inside each phase's segments."""
    by: dict[str, float] = {}
    i = 0
    for a, b in gaps:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s, e, phase = segments[j]
            got = min(b, e) - max(a, s)
            if got > 0:
                by[phase] = by.get(phase, 0.0) + got
            j += 1
    return by


def pair_prefills(host: list[tuple], modules: list[tuple]) -> list[dict]:
    """Each engine.prefill_dispatch span with the device's prefill module
    event it launched and its request's engine.first_token. The device runs
    programs in the order they were dispatched, so the n-th span launched the
    n-th module event; a capture cuts both lists, so a span takes the first
    unclaimed event of its program that starts after the span did."""
    mods = sorted(((s, d, tr.module_of(n)[0]) for n, s, d in modules
                   if "prefill" in n), key=lambda e: e[0])
    firsts = {st.get("request"): s for n, s, _d, st in host
              if n == "engine.first_token" and "request" in st}
    pairs, k = [], 0
    for n, s, d, st in sorted(host, key=lambda e: e[1]):
        if n != "engine.prefill_dispatch" or "program" not in st:
            continue
        while k < len(mods) and (mods[k][0] < s or not mods[k][2].endswith(
                str(st["program"]))):
            k += 1
        if k == len(mods):
            break
        m_start, m_dur, _name = mods[k]
        k += 1
        pairs.append({**st, "span_start": s, "span_s": d,
                      "module_start": m_start, "module_s": m_dur,
                      "first_token_start": firsts.get(st.get("request"))})
    return pairs


def reduce(path: str) -> dict:
    cap = read_capture(path)
    host = cap["host"]
    if not host:
        return {"spans": {}}
    table: dict[str, dict] = {}
    for n, _s, d, _st in host:
        row = table.setdefault(n, {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += d
    out = {"spans": table,
           "events": {k: [[s, d, st] for n, s, d, st in host if n == k]
                      for k in KEPT}}
    if cap["window"] is None or not cap["ops"]:
        return out
    lo, hi = cap["window"]
    busy = tr.union([(s, s + d) for s, d in cap["ops"]])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    segments = phase_segments(host)
    if not segments:
        return out
    # The profiler records a span only if it was tracing when the span began
    # and when it ended, so the step that was open at either end of the
    # capture is missing: idle time is attributed over what the spans cover.
    a0, b0 = segments[0][0], max(e for _s, e, _p in segments)
    everywhere = sum(b - a for a, b in gaps)
    gaps = [(max(a, a0), min(b, b0)) for a, b in gaps
            if min(b, b0) > max(a, a0)]
    idle_s = sum(b - a for a, b in gaps)
    by = overlap(gaps, segments)
    by["unattributed"] = max(0.0, idle_s - sum(by.values()))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    mods = sorted(cap["modules"], key=lambda e: e[1])
    out.update({
        "window": [lo, hi], "covered": [a0, b0], "idle_s": idle_s,
        "idle_outside_s": everywhere - idle_s, "idle_by_phase": by,
        "longest_gaps": [[a - lo, b - a, overlap([(a, b)], segments)]
                         for a, b in longest],
        "prefills": pair_prefills(host, cap["modules"]),
        "prefill_modules": sum(1 for n, _s, _d in mods if "prefill" in n),
        "decode_module_ends": [s + d for n, s, d in mods
                               if "decode_chunk" in n],
    })
    return out


def show(red: dict) -> None:
    """The tables a run prints before its result line."""
    if not red["spans"]:
        print("spans: no engine.* or cell.* event in this capture (a program "
              "without obs/spans.py)")
        return
    print("spans: name / count / seconds")
    for name, row in sorted(red["spans"].items()):
        print(f"  {name:<26} {row['count']:>6} {row['seconds']:>10.4f}")
    if "idle_s" not in red:
        print("spans: no device operation in this capture; host side only")
        return
    idle = red["idle_s"]
    print(f"device 0 idle by the engine loop's phase: {idle:.4f} s idle in the "
          f"{red['covered'][1] - red['covered'][0]:.4f} s the recorded spans "
          f"cover, of a window of {red['window'][1] - red['window'][0]:.4f} s "
          f"({red['idle_outside_s']:.4f} s idle at its ends, where the open "
          "step was not recorded)")
    for phase, s in sorted(red["idle_by_phase"].items(), key=lambda kv: -kv[1]):
        print(f"  {phase:<16} {s:>9.4f} s {100 * s / idle if idle else 0:>6.1f}%")
    print("longest idle gaps: at s / ms / host phases (ms)")
    for at, dur, by in red["longest_gaps"]:
        what = ", ".join(f"{p} {s * 1e3:.2f}" for p, s in
                         sorted(by.items(), key=lambda kv: -kv[1]))
        print(f"  {at:>8.4f} {dur * 1e3:>8.3f}  {what or 'unattributed'}")
    whole = [p for p in red["prefills"]
             if p["first_token_start"] is not None]
    print(f"prefills: {len(red['events']['engine.prefill_dispatch'])} "
          f"dispatch spans, {red['prefill_modules']} prefill module events on "
          f"device 0, {len(red['prefills'])} paired, {len(whole)} with their "
          "first token in the capture")
    for p in whole:
        print(f"  {p.get('program')}: real {p.get('real')} padded "
              f"{p.get('padded')} cached {p.get('cached')}; dispatch -> "
              f"module start {(p['module_start'] - p['span_start']) * 1e3:.1f}"
              f" ms, module {p['module_s'] * 1e3:.1f} ms, module end -> "
              "first token "
              f"{(p['first_token_start'] - p['module_start'] - p['module_s']) * 1e3:.1f} ms")
    lags = fetch_lags(red)
    if lags:
        mid = sorted(lags)[len(lags) // 2]
        print(f"clocks: engine.fetch_chunk ends {mid * 1e3:.3f} ms (median; "
              f"min {min(lags) * 1e3:.3f}, max {max(lags) * 1e3:.3f}, n "
              f"{len(lags)}) after the nearest end of a decode_chunk module")


def fetch_lags(red: dict) -> list[float]:
    """For each engine.fetch_chunk span, its end less the nearest end of a
    decode_chunk module event on device 0: positive and small when the two
    clocks agree (the fetch returns once its chunk has run)."""
    ends = red.get("decode_module_ends") or []
    if not ends:
        return []
    return [min((s + d - e for e in ends), key=abs)
            for s, d, _st in red["events"]["engine.fetch_chunk"]]


if __name__ == "__main__":
    reduction = reduce(sys.argv[1])
    show(reduction)
    sys.stdout.flush()
    with open(sys.argv[2], "w") as f:
        json.dump(reduction, f)
