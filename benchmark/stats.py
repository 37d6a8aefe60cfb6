"""Metric arithmetic of the benchmark: percentiles, the SLO share, the
generator's lateness, and the reading of a Prometheus text exposition.

Kept here, under the benchmark's paths, so that every PR computes the same
number the same way.
"""

from __future__ import annotations

import math
import re


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q percent of
    the sample at or below it. No interpolation, so it is always a reading."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def tpot_ms(first_s: float, last_s: float, tokens: int) -> float | None:
    """A request's mean gap between tokens; None for a one-token answer."""
    if tokens < 2:
        return None
    return (last_s - first_s) / (tokens - 1) * 1e3


def end_to_end(records: list[dict], seconds: float, limits: dict,
               tokens_in_window: int, censor_ms: float) -> dict:
    """The client-side metrics over every request SENT in the window (which
    of them a cell reports under which heading is BENCHMARK.json's choice).

    ``records`` carry ``ok``, ``ttft_ms``, ``tpot_ms`` (None for a failed
    request or a one-token answer) and ``latency_ms`` (due -> last token: the
    whole answer, which is what a caller that acts on answers waits for). A
    failed, refused or undrained request stays in every tail and mean at
    ``censor_ms`` and misses the limits."""
    if not records:
        raise ValueError("no request was sent in the window")
    ttft = [r["ttft_ms"] if r["ok"] else censor_ms for r in records]
    tpot = [r["tpot_ms"] if r["ok"] else censor_ms for r in records
            if not r["ok"] or r["tpot_ms"] is not None]
    latency = [r["latency_ms"] if r["ok"] else censor_ms for r in records]
    met = sum(1 for r in records if r["ok"]
              and r["ttft_ms"] <= limits["ttft_ms"]
              and (r["tpot_ms"] is None or r["tpot_ms"] <= limits["tpot_ms"]))
    return {
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p90_ms": percentile(ttft, 90),
        "ttft_mean_ms": sum(ttft) / len(ttft),
        "tpot_p50_ms": percentile(tpot, 50),
        "tpot_p90_ms": percentile(tpot, 90),
        "tpot_mean_ms": sum(tpot) / len(tpot),
        "latency_mean_ms": sum(latency) / len(latency),
        "slo_share": 100.0 * met / len(records),
        "out_tok_per_s": tokens_in_window / seconds,
    }


def lateness_ms(records: list[dict]) -> dict:
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    return {"p50": percentile(late, 50), "max": max(late), "n": len(late)}


# --- Prometheus text exposition --------------------------------------------

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> dict:
    """{family: [(labels dict, value)]} of a text exposition."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
    return out


def sample(metrics: dict, family: str, **labels) -> float:
    """Sum of a family's samples whose labels include ``labels``; 0 where the
    family or the label set is absent."""
    return sum(v for lb, v in metrics.get(family, [])
               if all(lb.get(k) == w for k, w in labels.items()))


def delta(before: dict, after: dict, family: str, **labels) -> float:
    return sample(after, family, **labels) - sample(before, family, **labels)


def histogram_quantile(before: dict, after: dict, family: str,
                       q: float) -> float | None:
    """Quantile of what a histogram observed between two scrapes, by linear
    interpolation inside the bucket, as Prometheus does it. Seconds."""
    edges = {}
    for lb, v in after.get(family + "_bucket", []):
        edges[lb["le"]] = v - sum(
            w for lb0, w in before.get(family + "_bucket", [])
            if lb0.get("le") == lb["le"])
    if not edges:
        return None
    ordered = sorted(edges.items(),
                     key=lambda kv: math.inf if kv[0] == "+Inf"
                     else float(kv[0]))
    total = ordered[-1][1]
    if total <= 0:
        return None
    want, lo, seen = q / 100.0 * total, 0.0, 0.0
    for le, cum in ordered:
        hi = math.inf if le == "+Inf" else float(le)
        if cum >= want:
            if math.isinf(hi):
                return lo
            return lo + (hi - lo) * (want - seen) / max(cum - seen, 1e-12)
        lo, seen = hi, cum
    return lo


def mean_live(records: list[dict], t0: float, t1: float) -> dict:
    """Mean, over [t0, t1] of the client's clock, of the requests holding a
    slot (first token seen, last not yet) and of the KV rows they hold (the
    prompt plus the tokens received so far)."""
    span = max(t1 - t0, 1e-9)
    slots = rows = 0.0
    for r in records:
        times = r.get("token_times") or []
        if len(times) < 2:
            continue
        a, b = max(times[0], t0), min(times[-1], t1)
        if b <= a:
            continue
        slots += (b - a) / span
        inside = [t for t in times if a <= t <= b]
        mid = r["prompt_len"] + (times.index(inside[0]) + len(inside) / 2.0
                                 if inside else len(times) / 2.0)
        rows += mid * (b - a) / span
    return {"slots": slots, "kv_rows": rows}
