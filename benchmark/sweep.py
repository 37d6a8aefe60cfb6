"""The knee of a cell, found once by hand on the chip: one boot, then the same
mix offered at several rates, each for its own window. Prints one JSON line per
rate: the tails, the tokens per second, and the backlog (requests sent and not
finished) at the middle and at the end of the window. The knee is the highest
rate at which the backlog at the end is no larger than at the middle. No
reference check here; PERF.md records the sweep.

    python benchmark/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --key <rate parameter of the mix> --rates r1,r2,...
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loadgen, plugins, run, stats  # noqa: E402

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    spec, child, _run_dir = run.open_cell(args.workload, args.seed, "sweep")
    config, traffic = spec["config"], spec["traffic"]
    try:
        port = child.start()["port"]
        run.warm_up(port, traffic, config["vocab_size"], args.seed,
                    config["serving"]["max_seq_len"])
        warm = run.scrape(port)
        gen_mod = plugins.load("generators", traffic["generator"],
                               spec["pkg_dir"])
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            params = {**traffic["params"], args.key: rate}
            gen = gen_mod.Generator(params, args.seed + i,
                                    config["vocab_size"], args.seconds)
            t0 = time.monotonic() + float(params.get("ramp_s", 0.0)) + 0.25
            records = loadgen.OpenLoop(port, gen, t0, args.seconds,
                                       traffic["drain_s"]).run()
            censor = (args.seconds + traffic["drain_s"]) * 1e3
            window = run.client_records(
                [r for r in records if r["in_window"]], censor)
            toks = sum(1 for r in records for t in r["token_times"]
                       if t0 <= t < t0 + args.seconds)

            def backlog(t):
                return sum(1 for r in records if r["sent"] <= t
                           and (r["done"] is None or r["done"] > t))

            e2e = stats.end_to_end(window, args.seconds, traffic["limits"],
                                   toks, censor)
            ok = [r for r in window if r["ok"]]
            print("SWEEP " + json.dumps({
                "rate": rate, "sent": len(window),
                "failed": len(window) - len(ok),
                "req_per_s": len(window) / args.seconds, **e2e,
                "ttft_p50_ms": stats.percentile(
                    [r["ttft_ms"] for r in window], 50),
                "backlog_mid": backlog(t0 + args.seconds / 2),
                "backlog_end": backlog(t0 + args.seconds),
                "lateness_ms": stats.lateness_ms(records),
                "compiles": stats.delta(warm, run.scrape(port),
                                        run.COMPILES)}), flush=True)
    finally:
        child.close()
