#!/usr/bin/env python3
"""Diff the newest BENCH_rNN.json trajectory artifact against the
previous round and flag regressions.

The bench artifacts (`bench.py --out BENCH_rNN.json`, schema
kukeon-bench/v1..v8) are the repo's performance trajectory; this tool is
the cheap guard that a round did not silently give back throughput,
latency, cold start, or HBM headroom:

    python tools/bench_compare.py                 # newest vs previous
    python tools/bench_compare.py --threshold 5   # stricter gate (%)
    python tools/bench_compare.py A.json B.json   # explicit pair (old new)

Exit codes: 0 = no regression past the threshold (or fewer than two
comparable artifacts — early rounds logged raw run transcripts, not
artifacts, and those are skipped, not errors), 1 = regression, 2 = usage.
Wired into tools/check.sh as an informational step. No BENCH_r*.json is
committed (the record of chip runs is PERF_LEDGER.jsonl and PERF.md); on an
empty directory this exits 0 with nothing to do.

Zero dependencies on bench.py (which imports jax): the schema-upgrade
shim here mirrors bench.read_artifact and is pinned against it by
tests/test_tsdb.py.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

SCHEMAS = ("kukeon-bench/v1", "kukeon-bench/v2", "kukeon-bench/v3",
           "kukeon-bench/v4", "kukeon-bench/v5", "kukeon-bench/v6",
           "kukeon-bench/v7", "kukeon-bench/v8")

# (label, path into the artifact, direction: +1 = higher is better)
METRICS = (
    ("tok/s", ("tok_per_s",), +1),
    # v8: the roofline headline — the busiest program's model-FLOPs
    # utilization from the engine's own ProgramTimers. A drop at equal
    # tok/s means the same throughput now burns more device time.
    ("MFU", ("mfu",), +1),
    ("ttft p95 (s)", ("latency_s", "ttft", "p95"), -1),
    # v4: the top-level client-observable TTFT p95 (disagg runs measure it
    # through the gateway; classic runs lift it from latency_s) and the KV
    # handoff cost — a regression here means the prefill->decode transfer
    # path got slower, the disaggregation's whole budget.
    ("ttft p95 (s, v4)", ("ttft_p95_s",), -1),
    ("handoff p50 (ms)", ("handoff_ms_p50",), -1),
    ("e2e p95 (s)", ("latency_s", "e2e", "p95"), -1),
    ("cold start p50 (s)", ("cold_start", "p50_s"), -1),
    # v6: the streamed-boot load sub-phases (work-time medians off the
    # cell's own gauges). These overlap each other and compile, so a
    # regression in any one of them names WHICH leg of the boot pipeline
    # got slower even when the overlapped total hides it.
    ("cold disk (s)", ("cold_start", "load_s", "disk"), -1),
    ("cold cast (s)", ("cold_start", "load_s", "cast"), -1),
    ("cold upload (s)", ("cold_start", "load_s", "upload"), -1),
    ("peak HBM (bytes)", ("peak_hbm_bytes",), -1),
    # v5: the diurnal ramp's headline numbers — the peak stage's client
    # p95 (the latency the spillover queue trades a shed storm for) and
    # failed requests over the whole ramp (contract: zero).
    ("diurnal peak p95 (s)", ("diurnal", "peak_p95_s"), -1),
    ("diurnal failed", ("diurnal", "failed"), -1),
)


def read_artifact(path: str) -> dict | None:
    """A BENCH_rNN.json if it is a bench artifact (any schema version),
    upgraded to the v8 shape; None for the early raw-transcript rounds."""
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(artifact, dict) or artifact.get("schema") not in SCHEMAS:
        return None
    if artifact["schema"] != "kukeon-bench/v8":
        artifact = dict(artifact)
        artifact.setdefault("replicas", 1)
        artifact.setdefault("kv_page_tokens", 0)
        artifact.setdefault("max_sessions", artifact.get("sessions"))
        lat = ((artifact.get("latency_s") or {}).get("ttft") or {})
        artifact.setdefault("ttft_p95_s", lat.get("p95"))
        artifact.setdefault("handoff_ms_p50", None)
        artifact.setdefault("disagg", None)
        artifact.setdefault("diurnal", None)
        if isinstance(artifact.get("cold_start"), dict):
            artifact["cold_start"] = dict(artifact["cold_start"])
            artifact["cold_start"].setdefault("load_s", None)
        artifact.setdefault("mesh", None)
        artifact.setdefault("program_costs", None)
        artifact.setdefault("mfu", None)
        artifact["schema"] = "kukeon-bench/v8"
    return artifact


def _dig(artifact: dict, path: tuple[str, ...]) -> float | None:
    cur: object = artifact
    for key in path:
        if not isinstance(cur, dict) or cur.get(key) is None:
            return None
        cur = cur[key]
    try:
        return float(cur)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        return None


def find_rounds(directory: str) -> list[tuple[int, str, dict]]:
    """(round number, path, artifact) for every parseable BENCH_rNN.json,
    sorted by round."""
    out = []
    for path in glob.glob(os.path.join(directory, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        artifact = read_artifact(path)
        if artifact is not None:
            out.append((int(m.group(1)), path, artifact))
    return sorted(out)


def compare(prev: dict, new: dict, threshold_pct: float
            ) -> tuple[list[tuple[str, float | None, float | None,
                                  float | None, str]], bool]:
    """Per-metric rows (label, prev, new, delta %, verdict) and whether
    any shared metric regressed past the threshold. A metric missing on
    either side is reported but never a regression — early artifacts
    lack fields later rounds added."""
    rows = []
    regressed = False
    for label, path, direction in METRICS:
        a, b = _dig(prev, path), _dig(new, path)
        if a is None or b is None:
            rows.append((label, a, b, None, "n/a"))
            continue
        if a == 0:
            rows.append((label, a, b, None, "n/a"))
            continue
        delta_pct = (b - a) / abs(a) * 100.0
        worse = -delta_pct * direction
        if worse > threshold_pct:
            rows.append((label, a, b, delta_pct, "REGRESSION"))
            regressed = True
        elif -worse > threshold_pct:
            rows.append((label, a, b, delta_pct, "improved"))
        else:
            rows.append((label, a, b, delta_pct, "ok"))
    return rows, regressed


def _fmt(v: float | None) -> str:
    if v is None:
        return "-"
    if abs(v) >= 1e6 and v == int(v):
        return f"{v:.3e}"
    return f"{v:.4g}"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python tools/bench_compare.py",
        description="diff the two newest bench trajectory artifacts")
    parser.add_argument("artifacts", nargs="*",
                        help="explicit OLD NEW artifact paths (default: "
                             "the two newest BENCH_rNN.json)")
    parser.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_rNN.json (default: the repo root)")
    parser.add_argument("--threshold", type=float, default=10.0,
                        help="regression tolerance in percent "
                             "(default 10)")
    args = parser.parse_args(argv)

    if args.artifacts and len(args.artifacts) != 2:
        print("error: give exactly two artifact paths (old new), or none",
              file=sys.stderr)
        return 2
    if args.artifacts:
        pair = []
        for path in args.artifacts:
            artifact = read_artifact(path)
            if artifact is None:
                print(f"error: {path} is not a bench artifact "
                      f"(schema {SCHEMAS})", file=sys.stderr)
                return 2
            pair.append((path, artifact))
        (prev_path, prev), (new_path, new) = pair
    else:
        rounds = find_rounds(args.dir)
        if len(rounds) < 2:
            print(f"bench_compare: {len(rounds)} comparable artifact(s) "
                  f"under {args.dir} — need two rounds to diff; nothing "
                  "to do")
            return 0
        (_n0, prev_path, prev), (_n1, new_path, new) = rounds[-2:]

    if prev.get("backend") != new.get("backend"):
        print(f"bench_compare: NOTE backend changed "
              f"{prev.get('backend')!r} -> {new.get('backend')!r} — "
              "deltas compare different hardware")
    print(f"bench_compare: {os.path.basename(prev_path)} -> "
          f"{os.path.basename(new_path)} "
          f"(threshold {args.threshold:g}%)")
    rows, regressed = compare(prev, new, args.threshold)
    fmt = "{:<20} {:>12} {:>12} {:>9} {}"
    print(fmt.format("METRIC", "PREV", "NEW", "DELTA", "VERDICT"))
    for label, a, b, delta, verdict in rows:
        print(fmt.format(label, _fmt(a), _fmt(b),
                         "-" if delta is None else f"{delta:+.1f}%",
                         verdict))
    if regressed:
        print(f"bench_compare: regression past {args.threshold:g}% — "
              "inspect the newest round before shipping")
        return 1
    print("bench_compare: no regression past the threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
