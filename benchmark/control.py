"""The control of the correctness check, run by hand on the chip (never by a
benchmark run): one run of a cell with a short window, after which the
reference is also computed in the precisions below the configuration's (int8
activations and KV; int4 weights) over the same prompts and served tokens.
PERF.md records the readings the limits were set from.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s>
"""

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec, child, run_dir = run.open_cell(args.workload, args.seed, "control")
    try:
        out = run.drive(child, spec, args.seed, args.seconds, False, run_dir,
                        T0, controls=("a8", "w4"))
    finally:
        child.close()
    print(json.dumps(out))
