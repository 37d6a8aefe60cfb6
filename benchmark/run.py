"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It reads the cell, its configuration file and
its traffic file, starts cell_main.py as the child that holds the chip(s),
warms up the program shapes the traffic file declares, offers the seeded load
to the cell's own HTTP server for ``--seconds``, has the child compare a
sample of what was served with the plain reference, and prints the result as
the last line. Nothing in this file names a cell, a configuration, a mix or a
metric: those are entries of BENCHMARK.json and files found by their names.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()      # set-up is counted from here

import argparse
import json
import os
import random
import shutil
import subprocess
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import loadgen, plugins, stats  # noqa: E402

COMPILES = "kukeon_compiles_total"
LATENESS_P50_MS = 5.0       # a generator later than this at the median starved
SEED_MOD = 2147483629       # weights' key: any --seed folded into 31 bits


def say(msg: str) -> None:
    print(msg, flush=True)


def no_result_line(stage: str, err: BaseException) -> str:
    return f"benchmark: no result: {stage}: {plugins.one_line(err)}"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench_dir: str, workload: str) -> dict:
    """The cell's entry with its configuration, traffic file and metrics."""
    bench = load_json(os.path.join(bench_dir, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(bench_dir, entry["file"])
    pkg_dir = os.path.dirname(os.path.dirname(config_path))
    config = load_json(config_path)
    traffic = load_json(os.path.join(pkg_dir, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "config_path": config_path, "pkg_dir": pkg_dir,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


class SubprocessCell:
    """cell_main.py as a child; JSON lines both ways."""

    def __init__(self, config_path: str, seed: int, run_dir: str,
                 warm_prompt_len: int):
        self.argv = [sys.executable, os.path.join(HERE, "cell_main.py"),
                     "--config", config_path, "--seed", str(seed),
                     "--run-dir", run_dir,
                     "--warm-prompt-len", str(warm_prompt_len)]
        self.proc = None

    def _read(self, kind: str) -> dict:
        """Next BENCH record of ``kind``; other lines pass through."""
        said = ""
        for line in self.proc.stdout:
            if line.startswith("BENCH "):
                rec = json.loads(line[6:])
                if rec["kind"] == kind:
                    return rec
                if rec["kind"] == "failed":
                    said = f" ({rec['error']})"
            else:
                say("cell: " + line.rstrip())
        raise SystemExit(f"benchmark: the cell process ended (exit code "
                         f"{self.proc.wait()}) before its {kind!r} record"
                         f"{said}. No result.")

    def start(self) -> dict:
        self.proc = subprocess.Popen(
            self.argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, cwd=REPO, bufsize=1)
        return self._read("ready")

    def command(self, msg: dict) -> dict:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        return self._read("reply")

    def close(self) -> None:
        if self.proc is None or self.proc.poll() is not None:
            return
        try:
            self.proc.stdin.write('{"cmd": "exit"}\n')
            self.proc.stdin.close()
            self.proc.wait(timeout=60)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()


def scrape(port: int) -> dict:
    status, body = loadgen.call(port, "GET", "/metrics")
    if status != 200:
        raise RuntimeError(f"/metrics answered {status}")
    return stats.parse_prometheus(body.decode())


def warm_up(port: int, traffic: dict, vocab: int, seed: int,
            rows: int) -> dict:
    """One request for each program shape the traffic file declares, then two
    identical greedy requests that have to return identical tokens."""
    rng = random.Random(seed * 7919 + 1)
    warm = traffic["warmup"]

    def tokens(n):
        return [rng.randrange(vocab) for _ in range(n)]

    def one(prompt, prefix_id=None, new=2):
        res = loadgen.post_generate(port, {
            "prompt": prompt, "max_new_tokens": new, "prefix_id": prefix_id},
            timeout_s=1500.0)
        if res["status"] != 200 or res["error"] or len(res["tokens"]) != new:
            raise SystemExit(f"benchmark: warm-up request failed: {res}. "
                             "No result.")
        return res["tokens"]

    t0 = time.monotonic()
    for b in warm["prefill"]:
        one(tokens(min(b, rows - 4)))     # a prompt has to leave rows free
    for i, (cached, tail) in enumerate(warm.get("prefill_ext", [])):
        head = tokens(cached // 2 + 1)
        one(head, prefix_id=f"warm-{seed}-{i}")
        one(head + tokens(tail), prefix_id=f"warm-{seed}-{i}")
    probe = tokens(min(warm["prefill"]) - 1)
    same = one(probe, new=8) == one(probe, new=8)
    return {"seconds": time.monotonic() - t0, "repeat_identical": same}


def pick_sample(records: list[dict], n: int, seed: int) -> list[dict]:
    """What the reference is run over: n served sequences, the longest and
    n - 1 others drawn from the seed, each with the spans of served tokens it
    holds. Requests under one prefixId whose prompts continue one another (a
    session's turns) travel in the last one's sequence, so one pass of the
    reference reads every answer of the session."""
    done = sorted((r for r in records if r["ok"] and r["in_window"]),
                  key=lambda r: r["id"])
    groups: dict = {}
    for i, r in enumerate(done):
        groups.setdefault(r.get("prefix_id") or f"\0{i}", []).append(r)
    carriers = []
    for key in sorted(groups):
        turns = sorted(groups[key], key=lambda r: r["prompt_len"])
        seq = turns[-1]["prompt"] + turns[-1]["tokens"]
        spans = [[r["prompt_len"] - 1, len(r["tokens"])] for r in turns
                 if seq[:r["prompt_len"] + len(r["tokens"])]
                 == r["prompt"] + r["tokens"]]
        carriers.append({"sequence": seq, "spans": spans})
    if not carriers:
        return []
    longest = max(range(len(carriers)),
                  key=lambda i: len(carriers[i]["sequence"]))
    rest = [c for i, c in enumerate(carriers) if i != longest]
    random.Random(seed * 31 + 5).shuffle(rest)
    return [carriers[longest]] + rest[:max(0, n - 1)]


def client_records(records: list[dict], censor_ms: float) -> list[dict]:
    out = []
    for r in records:
        tt = r["token_times"]
        out.append({**r, "ttft_ms": (tt[0] - r["due"]) * 1e3 if r["ok"]
                    else censor_ms,
                    "tpot_ms": stats.tpot_ms(tt[0], tt[-1], len(tt))
                    if r["ok"] else None,
                    "latency_ms": (tt[-1] - r["due"]) * 1e3 if r["ok"]
                    else censor_ms})
    return out


def drive(child, spec: dict, seed: int, seconds: float, trace: bool,
          run_dir: str, t_start: float, controls: tuple = ()) -> dict:
    """Everything after the device gate: boot, warm-up, window, capture,
    check, reduction. ``child`` has start(), command() and close(). A run
    that ends here without a result says at which stage, as one line on
    standard output, and ends as it would have (same exception, same exit
    code, every message that was there)."""
    stage = types.SimpleNamespace(name="boot")      # where the run is
    try:
        return _drive(child, spec, seed, seconds, trace, run_dir, t_start,
                      controls, stage)
    except BaseException as e:         # SystemExit too; always re-raised
        say(no_result_line(stage.name, e))
        raise


def _drive(child, spec, seed, seconds, trace, run_dir, t_start, controls,
           stage) -> dict:
    config, traffic = spec["config"], spec["traffic"]
    wseed = seed % SEED_MOD
    info = child.start()
    port, device = info["port"], info["device"]
    say(f"cell ready: device={json.dumps(device)} "
        f"boot_s={json.dumps(info['boot_phases_s'])}")
    say(f"levers: {json.dumps(info['levers'])}")

    stage.name = "warm-up"
    before_warm = scrape(port)
    warm = warm_up(port, traffic, config["vocab_size"], wseed,
                   config["serving"]["max_seq_len"])
    after_warm = scrape(port)
    say(f"warm-up: {warm['seconds']:.1f} s, compiles "
        f"{stats.delta(before_warm, after_warm, COMPILES):.0f}, "
        f"repeat identical: {warm['repeat_identical']}")

    stage.name = "window"
    gen_mod = plugins.load("generators", traffic["generator"],
                           spec["pkg_dir"])
    gen = gen_mod.Generator(traffic["params"], seed, config["vocab_size"],
                            seconds)
    ramp_s = float(traffic["params"].get("ramp_s", 0.0))
    scrapes: dict = {}
    scrape_late_ms: dict = {}
    capture: dict = {}
    t0 = time.monotonic() + ramp_s + 0.25

    def scrape_into(key, offset):
        def fn():
            scrape_late_ms[key] = (time.monotonic() - t0 - offset) * 1e3
            scrapes[key] = scrape(port)
        return fn

    def start_capture():
        try:
            capture["metrics_before"] = scrape(port)
            capture["requested"] = time.monotonic()
            status, body = loadgen.call(
                port, "POST", "/v1/profile",
                {"durationMs": capture["duration_s"] * 1e3})
            body = json.loads(body or b"{}")
            capture["status"] = status
            capture["rec"] = body.get("capture", body)
            time.sleep(capture["duration_s"])
            capture["metrics_after"] = scrape(port)
        except Exception as e:  # noqa: BLE001 (a thread's boundary)
            # this thread's exception reaches nobody: keep it for the line
            capture["error"] = f"{type(e).__name__}: {e}"

    close_at = seconds - 0.05
    window_scrapes = [(0.0, scrape_into("open", 0.0)),
                      (close_at, scrape_into("close", close_at))]
    at = list(window_scrapes)
    if trace:
        capture["duration_s"] = min(3.0, seconds / 3.0)
        capture["offset_s"] = min(5.0, seconds / 3.0)
        at.append((capture["offset_s"], start_capture))
    loop = loadgen.OpenLoop(port, gen, t0, seconds, traffic["drain_s"], at)
    setup_s = t0 - t_start
    records = loop.run()
    # A scrape that a stalled host kept the dispatcher from still runs, late,
    # and the run says how late; a capture is never started after the window.
    for _offset, fn in loop.missed:
        if any(fn is scrape_fn for _o, scrape_fn in window_scrapes):
            fn()
    final = scrape(port)
    if "open" not in scrapes or "close" not in scrapes:
        raise SystemExit("benchmark: the window's scrapes did not run. "
                         "No result.")
    say("window scrapes: " + ", ".join(
        f"{k} ran {scrape_late_ms[k]:.1f} ms late" for k in ("open", "close"))
        + f" (close is due {seconds - close_at:.2f} s before the window's end;"
        " later than that it counts work done after it)")

    censor_ms = (seconds + traffic["drain_s"]) * 1e3
    window = client_records([r for r in records if r["in_window"]], censor_ms)
    in_window = sum(1 for r in records for t in r["token_times"]
                    if t0 <= t < t0 + seconds)
    late = stats.lateness_ms(records)
    say(f"generator lateness: p50 {late['p50']:.3f} ms, max "
        f"{late['max']:.3f} ms over {late['n']} requests "
        f"({len(window)} in the window)")
    failed = [r for r in window if not r["ok"]]
    for r in failed[:5]:
        say(f"failed request {r['id']}: status {r['status']} {r['error']}")
    compiles = stats.delta(after_warm, final, COMPILES)

    if trace and "requested" not in capture and "error" not in capture:
        stage.name = "capture"
        raise SystemExit("benchmark: the capture was not started inside the "
                         "window: the dispatcher came to it too late. "
                         "No result.")
    if trace and capture.get("requested", t0) + capture["duration_s"] \
            > t0 + seconds:
        stage.name = "capture"
        raise SystemExit("benchmark: the capture began "
                         f"{capture['requested'] - t0:.1f} s into the window "
                         "and ran past its end. No result.")
    if trace and "metrics_after" not in capture:
        stage.name = "capture"
        raise SystemExit(
            f"benchmark: the capture did not finish: "
            f"{ {k: capture.get(k) for k in ('status', 'rec', 'error')} }"
            ". No result.")

    stage.name = "check"
    want = traffic["check"]
    sample = pick_sample(records, want["requests"], seed)
    check = child.command({
        "cmd": "check", "pad_to": config["serving"]["max_seq_len"],
        "controls": list(controls), "requests": sample,
    }) if sample else None
    child.close()

    limits = config["check"]["limits"]
    checks = {
        "repeat_identical": warm["repeat_identical"],
        "no_compile_in_window": compiles == 0,
        "every_answer_whole": not failed,
        "lateness_p50_under_5ms": late["p50"] <= LATENESS_P50_MS,
        "reference": check is not None and all(
            check[k] <= limits[k] for k in limits),
    }
    # Every number that `correct` compares, beside the limit it may not pass.
    compared = {k: [check[k], limits[k]] for k in limits} if check else {}
    compared.update({
        "compiles_in_window": [int(compiles), 0],
        "failed_requests": [len(failed), 0],
        "lateness_p50_ms": [late["p50"], LATENESS_P50_MS],
        "repeats_differing": [0 if warm["repeat_identical"] else 1, 0]})
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in compared.items()}
    if check is not None:
        say("check: " + ", ".join(
            f"{k} {check[k]:.5f} (limit {limits[k]})" for k in limits)
            + f"; {check['tokens']} served tokens ({check['flipped']} off the "
            f"reference's best) of {check['requests']} requests in "
            f"{check['sequences']} sequences, logit std {check['logit_std']:.3f}, reference "
            f"{check['reference_s']} s")
        say(f"freed for the reference: {check['freed_arrays']} arrays, "
            f"{check['freed_bytes']} bytes; in use on the fullest device when "
            f"it started: {check['bytes_in_use_under_reference']} bytes")
        for c in controls:
            say(f"control {c}: {json.dumps(check['control_' + c])}")
    say(f"compiles in the window: {compiles:.0f} (limit 0); failed requests: "
        f"{len(failed)} of {len(window)} (limit 0); checks: "
        f"{json.dumps(checks)}")

    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": check["memory_peak_bytes"]
                  if check else 0}
    result = {"correct": all(checks.values()), "attempted": len(window),
              "failed": len(failed), "metrics": {}, "device": device_out}
    client = stats.end_to_end(window, seconds, traffic["limits"], in_window,
                              censor_ms)
    client["setup_s"] = setup_s
    say(f"client: {json.dumps(client)}")
    if trace:
        stage.name = "reduction"
        reduced = reduce_trace(capture, run_dir)
        used = reduced["devices"][:config["serving"]["chips"]]
        device_out["busy_s"] = sum(d["busy_s"] for d in used) / len(used)
        device_out["window_s"] = sum(d["window_s"] for d in used) / len(used)
        cap0 = capture["requested"]
        ctx = {
            "trace": reduced, "capture": capture, "device": device,
            "client": client,
            "levers": info["levers"],
            "metrics_open": scrapes["open"], "metrics_close": scrapes["close"],
            "cell": spec["cell"], "config": config, "traffic": traffic,
            "pkg_dir": spec["pkg_dir"],
            "peaks": load_json(os.path.join(HERE, "peaks.json")),
            "live": stats.mean_live(records, cap0,
                                    cap0 + capture["duration_s"]),
            "records": records, "window": (t0, t0 + seconds),
        }
        for m in spec["per_layer"]:
            reader = plugins.load("layer_metrics", m["name"], spec["pkg_dir"])
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": used[0]["top_ops"][:10],
                               "idle_gaps": used[0]["idle_gaps"][:10]}
    else:
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": client[m["name"]],
                                            "unit": m["unit"]}
    # The checks by name, then every number compared beside its limit: the
    # result's last key, and this run's last lines on standard error.
    result["window_scrapes_late_ms"] = scrape_late_ms
    result["checks"] = checks
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared: {k} {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def reduce_trace(capture: dict, run_dir: str) -> dict:
    """trace_reduce.py in a process of its own (it reads the capture with
    jax.profiler.ProfileData, on the CPU backend, after the cell has gone)."""
    if capture.get("status") != 200:
        raise SystemExit(f"benchmark: /v1/profile answered {capture}. "
                         "No result.")
    out = os.path.join(run_dir, "reduction.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         capture["rec"]["path"], out], env=env, cwd=REPO)
    if done.returncode != 0:
        raise SystemExit(f"benchmark: trace_reduce.py exited with code "
                         f"{done.returncode} over {capture['rec']['path']}. "
                         "No result.")
    return load_json(out)


def open_cell(workload: str, seed: int, tag: str):
    """(spec, child, run_dir) of a cell of this repo's BENCHMARK.json; the
    run's files go to a fixed directory inside the checkout."""
    spec = load_cell(REPO, workload)
    run_dir = os.path.join(REPO, ".bench_runs", f"{workload}-{tag}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    child = SubprocessCell(spec["config_path"], seed % SEED_MOD, run_dir,
                           min(spec["traffic"]["warmup"]["prefill"]))
    return spec, child, run_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, child, run_dir = open_cell(args.workload, args.seed, str(args.trace))
    try:
        result = drive(child, spec, args.seed, args.seconds, bool(args.trace),
                       run_dir, _T0)
    finally:
        child.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
