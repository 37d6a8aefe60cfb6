"""What several per-layer readers share: picking a device's modules out of the
trace reduction, and lining the capture up with counters and client records.

A reader is ``read(ctx) -> float | None``; ``ctx`` holds the trace reduction
(``trace``), the two /metrics scrapes of the window (``metrics_open``,
``metrics_close``), the scrapes around the capture (``capture``), the
client's records, the cell, its configuration and traffic files, and the table
of peaks. A reader that finds nothing to read returns
None and the metric is left out of the line.
"""

from __future__ import annotations

from benchmark import stats


def device0(ctx: dict) -> dict:
    return ctx["trace"]["devices"][0]


def modules(ctx: dict, part: str) -> dict:
    """Count, seconds and events of device 0's modules whose name holds
    ``part`` (``prefill`` covers prefill and prefill_ext)."""
    out = {"count": 0, "seconds": 0.0, "events": []}
    for name, m in device0(ctx)["modules"].items():
        if part in name:
            out["count"] += m["count"]
            out["seconds"] += m["seconds"]
            out["events"] += m["events"]
    return out


def peaks(ctx: dict) -> dict:
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         "peaks.json. No result.")
    return ctx["peaks"][kind]


def capture_delta(ctx: dict, family: str, **labels) -> float:
    cap = ctx["capture"]
    return stats.delta(cap["metrics_before"], cap["metrics_after"], family,
                       **labels)


def decode_steps(ctx: dict) -> float | None:
    """Decode steps the traced decode modules ran, from the trace alone: an
    instruction of the layer scan's body runs layers x steps times, so the
    most-run instruction of each decode program over the layers is its steps
    (a chunk the capture cut in two counts the steps it caught).

    Holds for a decode program that is ONE scan over all
    ``num_hidden_layers`` equal layers (models/llama.py, models/moe.py). A
    family whose decode program is not (a leading dense layer before a scan
    over a period of four, say) brings readers of its own under new names,
    and lists its cells in their ``workloads``: every metric read through
    this function names its cells in BENCHMARK.json for that reason."""
    layers = ctx["config"]["num_hidden_layers"]
    most = 0
    for name, m in device0(ctx)["modules"].items():
        if "decode_chunk" in name:
            most += sum(m["max_op_count"].values())
    return most / layers if most else None


def prefills_in_capture(ctx: dict) -> list[dict]:
    """Client records whose first token arrived inside the capture."""
    c0 = ctx["capture"]["requested"]
    c1 = c0 + ctx["capture"]["duration_s"]
    return [r for r in ctx["records"]
            if r["token_times"] and c0 <= r["token_times"][0] <= c1]
