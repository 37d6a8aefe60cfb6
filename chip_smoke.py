#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path — a model-serving cell answering generate requests —
through the entry points a user calls, at the full width of llama3-8b
(hidden 4096, 32 q / 8 kv heads x 128, ffn 14336, vocab 128256, all 32
layers, int8 weights-only, random weights from the cell's fixed seed):

  phase 1  `python -m kukeon_tpu.runtime.serving_cell --model llama3-8b
           --dtype int8 --chips 1 --num-slots 4 --max-seq-len 4096`:
           warm every shape, then a request window (streamed, concurrent
           past the slot count, a prefix hit, a 1024+ token prompt, greedy
           repeats) with every status 200 and ZERO compiles, then a drain
           to exit code 0;
  phase 2  the daemon path: `make -C native`, `kuke init`, `kuke apply` of a
           one-cell manifest, one generate through the cell's address,
           `kuke delete`, daemon stopped — the second boot of the same
           programs, so its compile phase shows the compile cache hitting.

`--chips 4` runs ONLY the multi-chip path and what it is compared with:
chip-visibility probes, the tensor-parallel cell, and the same greedy
prompts through a one-chip cell.

This process never imports JAX: a chip belongs to one process at a time, and
the cells need it. The device triple on the last line is what the process
that held the chip reported on /v1/stats. Anything but a TPU is a failure;
`--model tiny` is a rehearsal of the control flow on any backend and never
prints a pass.

Last line of stdout:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
from http.client import HTTPConnection

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0          # the driver allows 1200 s, compilation included
T0 = time.monotonic()

# Per model: the serving shape and the request sizes that reach each
# program. Prompts of `short` tokens land in the prefill bucket the cell
# compiled at boot; `long` reaches a >= 1024 bucket, the sizes at which
# ops/attention.py's dispatcher considers the flash kernel (the "kernel
# paths" line says what it chose); `prefix` + `tail` is the
# prefix-extension pair.
SHAPES = {
    "llama3-8b": dict(max_seq_len=4096, short=48, long=1100,
                      prefix=60, tail=24, new_tokens=40, vocab=128256),
    "tiny": dict(max_seq_len=256, short=24, long=150,
                 prefix=60, tail=24, new_tokens=40, vocab=512),
}


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"smoke[{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - T0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(out_dir: str, extra: dict | None = None) -> dict:
    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if REPO not in parts:
        parts.insert(0, REPO)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    # Nothing the smoke needs may come from ~: the serving tune profile is
    # read at boot from ~/.kuke by default; point it into the output dir
    # (absent there), so the engine must boot at its defaults.
    env["KUKEON_TUNE_PATH"] = os.path.join(out_dir, "serving_tune.json")
    env.update(extra or {})
    return env


def cache_dir() -> str:
    """The compile cache directory the cells will use (the same rule as
    runtime/serving_cell.compilation_cache_dir, restated here because this
    process must not import the serving stack)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO, ".jax_cache"))


def cache_entries() -> int:
    try:
        return sum(1 for e in os.scandir(cache_dir()) if e.is_file())
    except OSError:
        return 0


# --- HTTP against a cell -------------------------------------------------------


def call(addr: tuple[str, int], method: str, path: str,
         body: dict | None = None, timeout: float = 600.0) -> tuple[int, bytes]:
    conn = HTTPConnection(addr[0], addr[1], timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, data,
                     {"Content-Type": "application/json"} if data else {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(addr, path):
    status, raw = call(addr, "GET", path, timeout=30)
    check(status == 200, f"GET {path} -> {status}")
    return json.loads(raw)


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def get_metrics(addr) -> dict[str, list[tuple[dict, float]]]:
    status, raw = call(addr, "GET", "/metrics", timeout=30)
    check(status == 200, f"GET /metrics -> {status}")
    fams: dict[str, list[tuple[dict, float]]] = {}
    for line in raw.decode().splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.split(" # ")[0].strip())
        if m:
            fams.setdefault(m.group(1), []).append(
                (dict(_LABEL.findall(m.group(2) or "")), float(m.group(3))))
    return fams


def by_label(fams, name: str, label: str) -> dict[str, float]:
    return {lab.get(label, ""): v for lab, v in fams.get(name, [])}


class Tally:
    """Status codes of every generate the smoke sent."""

    def __init__(self):
        self.lock = threading.Lock()
        self.statuses: dict[int, int] = {}

    def note(self, status: int) -> None:
        with self.lock:
            self.statuses[status] = self.statuses.get(status, 0) + 1

    def non_200(self) -> int:
        return sum(n for s, n in self.statuses.items() if s != 200)


def generate(addr, tally: Tally, prompt: list[int], new_tokens: int,
             label: str, prefix_id: str | None = None) -> list[int]:
    body = {"promptTokens": prompt, "maxNewTokens": new_tokens,
            "temperature": 0.0}
    if prefix_id:
        body["prefixId"] = prefix_id
    t0 = time.monotonic()
    status, raw = call(addr, "POST", "/v1/generate", body,
                       timeout=max(30.0, remaining()))
    tally.note(status)
    check(status == 200, f"generate[{label}] -> {status}: {raw[:300]!r}")
    out = json.loads(raw)
    check(out["numTokens"] == new_tokens == len(out["tokens"]),
          f"generate[{label}] returned {out['numTokens']} tokens, "
          f"wanted {new_tokens}")
    say(f"  request {label}: prompt {len(prompt)} -> {out['numTokens']} "
        f"tokens in {out['seconds']:.2f}s (wall {time.monotonic() - t0:.2f}s)")
    return out["tokens"]


def generate_stream(addr, tally: Tally, prompt: list[int], new_tokens: int,
                    label: str) -> list[int]:
    conn = HTTPConnection(addr[0], addr[1], timeout=max(30.0, remaining()))
    t0 = time.monotonic()
    try:
        conn.request("POST", "/v1/generate", json.dumps(
            {"promptTokens": prompt, "maxNewTokens": new_tokens,
             "temperature": 0.0, "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        tally.note(resp.status)
        check(resp.status == 200, f"stream[{label}] -> {resp.status}")
        toks, last = [], None
        for line in resp.read().decode().splitlines():
            last = json.loads(line)
            if "token" in last:
                toks.append(last["token"])
    finally:
        conn.close()
    check(last is not None and last.get("done") is True,
          f"stream[{label}] ended without a terminal record: {last}")
    check(last["tokens"] == toks and len(toks) == new_tokens,
          f"stream[{label}]: {len(toks)} streamed tokens, terminal record "
          f"has {last.get('numTokens')}, wanted {new_tokens}")
    say(f"  request {label} (streamed): prompt {len(prompt)} -> {len(toks)} "
        f"token lines + terminal record in {last['seconds']:.2f}s "
        f"(wall {time.monotonic() - t0:.2f}s)")
    return toks


# --- processes -----------------------------------------------------------------


class Procs:
    """Everything this script started, so that it can stop all of it."""

    def __init__(self):
        self.popen: list[subprocess.Popen] = []
        self.daemons: list[tuple[list[str], dict, str]] = []  # cli, env, run
        self.ports: list[int] = []

    def stop_all(self) -> None:
        for cli, env, run_path in self.daemons:
            for args in (["delete", "cell", "llm", "--force"],
                         ["daemon", "stop"]):
                try:
                    subprocess.run(cli + args, env=env, capture_output=True,
                                   timeout=60)
                except (OSError, subprocess.TimeoutExpired):
                    pass
            try:
                with open(os.path.join(run_path, "kukeond.pid")) as f:
                    os.kill(int(f.read().strip()), signal.SIGKILL)
            except (OSError, ValueError):
                pass
        for p in self.popen:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        # A supervised cell outlives its daemon by design; nothing of ours
        # may outlive the smoke.
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == os.getpid():
                continue
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    argv = f.read().decode(errors="replace").split("\0")
            except OSError:
                continue
            if any("kukeon_tpu.runtime" in a for a in argv) and any(
                    a == str(port) for port in self.ports for a in argv):
                try:
                    os.kill(int(pid), signal.SIGKILL)
                except OSError:
                    pass


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<{path}: {e}>"


def start_cell(procs: Procs, out_dir: str, name: str, model: str, chips: int,
               extra_env: dict | None = None):
    shape = SHAPES[model]
    port = free_port()
    procs.ports.append(port)
    cmd = [sys.executable, "-m", "kukeon_tpu.runtime.serving_cell",
           "--model", model, "--chips", str(chips), "--num-slots", "4",
           "--max-seq-len", str(shape["max_seq_len"]), "--port", str(port),
           "--dtype", "int8"]
    log_path = os.path.join(out_dir, f"{name}.log")
    say(f"starting {name}: {' '.join(cmd[1:])}")
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, env=child_env(out_dir, extra_env),
                                stdout=log, stderr=subprocess.STDOUT,
                                cwd=REPO if os.path.isdir(
                                    os.path.join(REPO, "kukeon_tpu")) else None)
    procs.popen.append(proc)
    return proc, ("127.0.0.1", port), log_path


def exited(proc: subprocess.Popen, log_path: str):
    """The ``died`` callable of :func:`wait_ready` for a cell this script
    started itself."""
    return lambda: (None if proc.poll() is None
                    else f"exit code {proc.returncode}\n{tail(log_path)}")


def wait_ready(addr, what: str, died) -> None:
    """Poll /readyz until 200; ``died()`` returns a reason string once the
    process behind ``addr`` is known dead."""
    t0 = time.monotonic()
    while True:
        why = died()
        check(why is None, f"{what} died before it was ready: {why}")
        check(remaining() > 0, f"{what} not ready inside the time budget")
        try:
            status, _ = call(addr, "GET", "/readyz", timeout=5)
            if status == 200:
                say(f"{what} ready after {time.monotonic() - t0:.1f}s")
                return
        except OSError:
            pass
        time.sleep(1.0)


def drain_to_exit(proc: subprocess.Popen, addr, what: str, log_path: str):
    status, _ = call(addr, "POST", "/drain", {}, timeout=30)
    check(status == 200, f"POST /drain -> {status}")
    try:
        rc = proc.wait(timeout=90)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{what} did not exit within 90s of /drain:\n"
                           f"{tail(log_path)}") from None
    check(rc == 0, f"{what} exited {rc} after drain:\n{tail(log_path)}")
    say(f"{what} drained, exit code 0")


# --- what a cell says about itself ----------------------------------------------


def device_of(stats: dict) -> dict:
    return {"platform": stats.get("platform"),
            "kind": stats.get("deviceKind"),
            "count": len(stats.get("devices", []))}


def report_boot(fams, label: str) -> dict[str, float]:
    phases = by_label(fams, "kukeon_cold_start_phase_seconds", "phase")
    total = fams.get("kukeon_cold_start_seconds", [({}, 0.0)])[0][1]
    say(f"{label} boot: total {total:.1f}s = " + " ".join(
        f"{k} {phases[k]:.1f}s" for k in
        ("imports", "init", "compile", "warmup", "serve") if k in phases))
    return phases


def report_cell(addr, label: str, rehearsal: bool) -> tuple[dict, dict]:
    stats = get_json(addr, "/v1/stats")
    fams = get_metrics(addr)
    dev = device_of(stats)
    say(f"{label} device (from the cell that holds it): {json.dumps(dev)} "
        f"devices={stats['devices']}")
    if not rehearsal:
        check(dev["platform"] == "tpu",
              f"{label} runs on platform {dev['platform']!r}, not a TPU")
    say(f"{label} mesh {stats['mesh']} tuning {stats['tuning']}")
    check(stats["tuning"]["fromProfile"] is False,
          f"{label} booted from a tune profile; the smoke wants defaults")
    used = by_label(fams, "kukeon_hbm_bytes_in_use", "device")
    limit = by_label(fams, "kukeon_hbm_bytes_limit", "device")
    peak = by_label(fams, "kukeon_hbm_bytes_peak", "device")
    for d in sorted(used):
        say(f"{label} HBM device {d}: in use {used[d] / 2**30:.2f} GiB, "
            f"peak {peak.get(d, 0) / 2**30:.2f} GiB, limit "
            f"{limit.get(d, 0) / 2**30:.2f} GiB")
        check(used[d] < limit.get(d, float("inf")),
              f"{label} HBM in use is not below the limit on device {d}")
    if not used:
        say(f"{label} HBM: the backend reports no memory stats")
        check(rehearsal, f"{label}: a TPU cell must report HBM in use")
    return stats, fams


def kernel_paths(fams) -> str:
    impl = {}
    for lab, v in fams.get("kukeon_op_impl_traces_total", []):
        impl.setdefault(lab["op"], {})[lab["impl"]] = int(v)
    return "; ".join(f"{op}: " + ", ".join(
        f"{'Pallas kernel' if i == 'pallas' else 'XLA'} x{n}"
        for i, n in sorted(paths.items())) for op, paths in sorted(impl.items()))


def compiles(fams) -> dict[str, float]:
    return by_label(fams, "kukeon_compiles_total", "program")


def prompts_for(model: str, seed: int) -> dict:
    """Token-id prompts, made from a seed with stdlib only."""
    shape = SHAPES[model]

    def ids(tag: str, n: int) -> list[int]:
        out, i = [], 0
        while len(out) < n:
            h = hashlib.sha256(f"{seed}/{tag}/{i}".encode()).digest()
            out += [1 + int.from_bytes(h[j:j + 4], "big") % (shape["vocab"] - 1)
                    for j in range(0, 32, 4)]
            i += 1
        return out[:n]

    return {
        "short": [ids(f"short{i}", shape["short"] - 3 * i) for i in range(6)],
        "long": ids("long", shape["long"]),
        "prefix": lambda tag: (ids(f"prefix-{tag}", shape["prefix"]),
                               ids(f"tail-{tag}", shape["tail"])),
    }


# --- phase 1: the serving cell, directly ------------------------------------------


def phase_serve(procs: Procs, out_dir: str, model: str, rehearsal: bool):
    shape = SHAPES[model]
    n_new = shape["new_tokens"]
    entries0 = cache_entries()
    say(f"compile cache: {cache_dir()} holds {entries0} entries before boot 1")
    # A short stall budget, so that the warm-up requests' compiles (each a
    # stall of many seconds on a chip this cell holds) make the watchdog
    # put its question to the device in-process.
    proc, addr, log_path = start_cell(
        procs, out_dir, "cell-1", model, chips=1,
        extra_env={"KUKEON_WATCHDOG_S": "10"})
    wait_ready(addr, "cell-1", exited(proc, log_path))
    stats, fams = report_cell(addr, "cell-1", rehearsal)
    boot1 = report_boot(fams, "cell-1")
    say(f"cell-1 compiles at boot by program: {compiles(fams)}")

    tally = Tally()
    P = prompts_for(model, seed=22)
    say("warm-up: every shape the request window will use, once")
    ref_short = generate(addr, tally, P["short"][0], n_new, "warm short")
    pre, tl = P["prefix"]("warm")
    generate(addr, tally, pre, 8, "warm prefix (miss)", prefix_id="warm")
    generate(addr, tally, pre + tl, 8, "warm prefix (hit)", prefix_id="warm")
    ref_long = generate(addr, tally, P["long"], 16, "warm long")
    # The window below admits a request while others decode, which clamps
    # the chunk to 4 steps; run that program once too.
    concurrently([(generate, addr, tally, P["short"][i], n_new,
                   f"warm concurrent {i}") for i in (1, 2, 3, 4, 5)])
    c0 = compiles(get_metrics(addr))
    hits0 = get_json(addr, "/v1/stats")["prefixCache"]["hits"]
    say(f"compiles after warm-up by program: {c0}")

    say("request window (compiles here must be 0)")
    t_win = time.monotonic()
    got_stream = generate_stream(addr, tally, P["short"][0], n_new, "stream")
    check(got_stream == ref_short,
          "greedy streamed output differs from the same request's earlier "
          f"answer: {got_stream[:8]} vs {ref_short[:8]}")
    concurrently([(generate, addr, tally, P["short"][i], n_new,
                   f"concurrent {i}") for i in range(6)])
    pre, tl = P["prefix"]("window")
    generate(addr, tally, pre, 8, "prefix (miss)", prefix_id="window")
    generate(addr, tally, pre + tl, 8, "prefix (hit)", prefix_id="window")
    again_long = generate(addr, tally, P["long"], 16, "long")
    check(again_long == ref_long,
          "greedy output of the 1024+ token prompt differs across a repeat: "
          f"{again_long} vs {ref_long}")
    again_short = generate(addr, tally, P["short"][0], n_new, "repeat short")
    check(again_short == ref_short,
          "greedy output differs across a repeat: "
          f"{again_short[:8]} vs {ref_short[:8]}")
    window_s = time.monotonic() - t_win

    stats, fams = report_cell(addr, "cell-1 (after traffic)", rehearsal)
    c1 = compiles(fams)
    grew = {p: c1[p] - c0.get(p, 0) for p in c1 if c1[p] != c0.get(p, 0)}
    say(f"request window: {window_s:.1f}s, statuses {tally.statuses}, "
        f"compiles in the window {grew or 0}")
    check(not grew, f"programs recompiled under traffic: {grew}")
    check(tally.non_200() == 0, f"non-200 answers: {tally.statuses}")
    hits = stats["prefixCache"]["hits"] - hits0
    check(hits == 1, f"prefix cache hits in the window: {hits}, wanted 1 "
                     "(prefill_ext did not run)")
    disp = by_label(fams, "kukeon_program_dispatch_total", "program")
    say(f"program dispatches: {({k: int(v) for k, v in disp.items()})}")
    check(disp.get("prefill_ext", 0) >= 2, "prefill_ext never dispatched")
    say(f"kernel paths taken (per trace): {kernel_paths(fams)}")
    probes = by_label(fams, "kukeon_watchdog_probes_total", "verdict")
    say(f"watchdog (budget 10s, in-process probe): probes by verdict "
        f"{({k: int(v) for k, v in probes.items()}) or 'none fired'}, trips "
        f"{int(fams.get('kukeon_watchdog_trips_total', [({}, 0)])[0][1])}; "
        "the cell was not killed")
    drain_to_exit(proc, addr, "cell-1", log_path)
    entries1 = cache_entries()
    say(f"compile cache: {entries1} entries after boot 1 "
        f"(+{entries1 - entries0})")
    check(entries1 > 0, f"the compile cache at {cache_dir()} is empty after "
                        "a whole boot")
    return device_of(stats), boot1, entries0


def concurrently(calls: list[tuple]) -> None:
    """Run ``fn(*args)`` for every (fn, *args) at once; re-raise the first
    failure."""
    errors: list[BaseException] = []

    def run(fn, *args):
        try:
            fn(*args)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=c) for c in calls]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(30.0, remaining()))
        check(not t.is_alive(), "a concurrent request never returned")
    if errors:
        raise errors[0]


# --- phase 2: the daemon path -----------------------------------------------------


def sha_bins() -> dict[str, str]:
    out = {}
    bin_dir = os.path.join(REPO, "kukeon_tpu", "runtime", "bin")
    for name in sorted(os.listdir(bin_dir)):
        with open(os.path.join(bin_dir, name), "rb") as f:
            out[name] = hashlib.sha256(f.read()).hexdigest()[:12]
    return out


def phase_daemon(procs: Procs, out_dir: str, model: str, rehearsal: bool,
                 boot1: dict, entries0: int) -> dict:
    shape = SHAPES[model]
    before = sha_bins()
    t0 = time.monotonic()
    mk = subprocess.run(["make", "-C", os.path.join(REPO, "native"), "-B",
                         "-j4"], capture_output=True, text=True, timeout=300)
    check(mk.returncode == 0, f"make -C native failed:\n{mk.stderr[-2000:]}")
    after = sha_bins()
    say(f"native supervisors rebuilt from native/*.cpp in "
        f"{time.monotonic() - t0:.1f}s; differ from the committed binaries: "
        f"{[n for n in after if after[n] != before.get(n)] or 'none'}")

    from kukeon_tpu.runtime.cells import namespace as nsb   # no jax inside

    run_path = os.path.join(out_dir, "run")
    sock = os.path.join(out_dir, "kuked.sock")
    check(len(sock) < 100, f"socket path too long for a unix socket: {sock}")
    env = child_env(out_dir, {"KUKEOND_RECONCILE_INTERVAL": "1.0"})
    if rehearsal and "KUKEON_TPU_CHIPS" not in env:
        env["KUKEON_TPU_CHIPS"] = "0"      # a host with no device nodes
    cli = [sys.executable, "-m", "kukeon_tpu.runtime.cli",
           "--run-path", run_path, "--socket", sock]
    isolated = nsb.available()
    say("isolation backend: " + (
        "namespace sandbox (kukecell), with hostNetwork: true — the cell "
        "keeps its own UTS/IPC/PID/mount namespaces and a /dev holding only "
        "the granted nodes; it shares the host's netns because the chip "
        "machine is itself a sandbox whose fresh sysfs for a new netns "
        "lists no PCI devices, and libtpu finds no chip without them"
        if isolated else "process backend (no root + kukecell here)"))

    def kuke(*args, stdin=None, timeout=120):
        p = subprocess.run(cli + list(args), env=env, input=stdin,
                           capture_output=True, text=True, timeout=timeout)
        check(p.returncode == 0, f"kuke {' '.join(args)} rc={p.returncode}\n"
                                 f"{p.stdout[-1500:]}\n{p.stderr[-1500:]}")
        return p.stdout

    procs.daemons.append((cli, env, run_path))
    say("kuke init: " + kuke("init").strip().replace("\n", " | "))
    status = json.loads(kuke("--json", "daemon", "status"))
    say(f"daemon sees TPU chips {status['tpuChips']}")
    port = free_port()
    procs.ports.append(port)
    manifest = (
        "apiVersion: kukeon.io/v1beta1\n"
        "kind: Cell\n"
        "metadata: {name: llm}\n"
        "spec:\n"
        f"  model: {{model: {model}, dtype: int8, chips: 1, numSlots: 4, "
        f"maxSeqLen: {shape['max_seq_len']}, port: {port}, "
        "hostNetwork: true}\n")
    say("kuke apply: " + kuke("apply", "-f", "-", stdin=manifest).strip())

    def cell_record() -> dict:
        return json.loads(kuke("--json", "get", "cells", "llm"))

    def died():
        st = cell_record()["status"]
        c = (st.get("containers") or [{}])[0]
        if c.get("state") == "exited" or (c.get("restarts") or 0) > 0:
            logs = subprocess.run(
                cli + ["log", "llm", "--container", "model-server"],
                env=env, capture_output=True, text=True, timeout=60).stdout
            return (f"container {c.get('state')} exit={c.get('exitCode')} "
                    f"restarts={c.get('restarts')} reason={st.get('reason')}\n"
                    f"{logs[-3000:]}")
        return None

    rec = cell_record()
    addr = (rec["status"].get("ip") or "127.0.0.1", port)
    say(f"cell llm: chips granted {rec['status'].get('tpuChips')}, address "
        f"{addr[0]}:{addr[1]}")
    wait_ready(addr, "cell llm (under the daemon)", died)
    if isolated:
        cell_dirs = [d for d, _s, files in os.walk(run_path)
                     if "sandbox.pid" in files]
        check(bool(cell_dirs), "namespace backend, but the cell has no sandbox")
        say(f"cell llm runs in sandbox {cell_dirs[0]}")
    stats, fams = report_cell(addr, "cell llm", rehearsal)
    boot2 = report_boot(fams, "cell llm")
    tally = Tally()
    P = prompts_for(model, seed=22)
    generate(addr, tally, P["short"][0], shape["new_tokens"],
             "through the daemon's cell")
    check(tally.non_200() == 0, f"non-200 answers: {tally.statuses}")
    entries2 = cache_entries()
    c1, c2 = boot1.get("compile", 0.0), boot2.get("compile", 0.0)
    say(f"compile phase: boot 1 {c1:.1f}s"
        f"{' (cache held entries already: not a cold boot)' if entries0 else ' (cold cache)'}"
        f", boot 2 {c2:.1f}s; cache entries after boot 2: {entries2}")
    if entries0 == 0:
        check(c2 <= 0.5 * c1, "the second boot's compile phase is not "
                              f"markedly shorter ({c2:.1f}s vs {c1:.1f}s): "
                              "the compile cache did not hit")
    else:
        check(c2 <= 1.5 * c1 + 5.0, f"the second boot compiled longer than "
                                    f"the first ({c2:.1f}s vs {c1:.1f}s)")
    say("kuke delete: " + kuke("delete", "cell", "llm", "--force").strip())
    say("kuke daemon stop: " + kuke("daemon", "stop").strip())
    try:
        call(addr, "GET", "/healthz", timeout=3)
        raise SmokeFailure("cell llm still answers after kuke delete")
    except OSError:
        pass
    return device_of(stats)


# --- --chips 4: only the multi-chip path and what it is compared with ------------


def visibility_probes(out_dir: str) -> None:
    """Processes under TPUDeviceManager.visibility_env see exactly their
    grant. Each pair runs CONCURRENTLY and holds its chips for a few
    seconds: a chip belongs to one process at a time, so two that both
    come up hold different chips."""
    from kukeon_tpu.runtime.devices import (   # no jax inside
        TPUDeviceManager,
        discover_chips,
    )

    host = discover_chips()
    check(len(host) == 4, f"this host shows chips {host}, not four")
    code = ("import jax, time, numpy; d = jax.devices(); "
            "jax.block_until_ready(jax.device_put(numpy.ones(8))); "
            "print('SEES', len(d), [str(x) for x in d], flush=True); "
            "time.sleep(8)")
    for grants in (([0], [1]), ([0, 1], [2, 3])):
        running = []
        for g in grants:
            vis = TPUDeviceManager.visibility_env(g, host)
            say(f"visibility probe, grant {g}: {vis}")
            running.append((g, subprocess.Popen(
                [sys.executable, "-c", code], env=child_env(out_dir, vis),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)))
        for g, p in running:
            try:
                out, err = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                raise SmokeFailure(f"visibility probe {g} hung") from None
            line = next((ln for ln in out.splitlines() if "SEES" in ln), None)
            check(p.returncode == 0 and line is not None,
                  f"visibility probe {g} failed rc={p.returncode}:\n"
                  f"{err[-1500:]}")
            say(f"  grant {g} -> {line}")
            check(int(line.split()[1]) == len(g),
                  f"grant {g} sees {line.split()[1]} chips, wanted {len(g)}")


def phase_four_chips(procs: Procs, out_dir: str, model: str,
                     rehearsal: bool) -> dict:
    from kukeon_tpu.runtime.devices import (   # no jax inside
        TPUDeviceManager,
        discover_chips,
    )

    if not rehearsal:
        visibility_probes(out_dir)
    shape = SHAPES[model]
    P = prompts_for(model, seed=22)
    prompts = P["short"] + [P["long"]]
    answers: dict[int, list[list[int]]] = {}
    device = None
    for chips in (4, 1):
        name = f"cell-{chips}chip"
        vis = (TPUDeviceManager.visibility_env([0], discover_chips())
               if chips == 1 and not rehearsal else None)
        proc, addr, log_path = start_cell(procs, out_dir, name, model, chips,
                                          extra_env=vis)
        wait_ready(addr, name, exited(proc, log_path))
        stats, fams = report_cell(addr, name, rehearsal)
        report_boot(fams, name)
        check(stats["mesh"]["chips"] == chips,
              f"{name} serves on {stats['mesh']['chips']} chips")
        if chips == 4:
            device = device_of(stats)
            check(rehearsal or stats["mesh"]["kvSharded"] is True,
                  "the 4-chip cell did not shard its KV cache over the "
                  "8 kv heads")
            used = by_label(fams, "kukeon_hbm_bytes_in_use", "device")
            if used:
                check(len(used) == 4 and max(used.values())
                      < 1.5 * min(used.values()),
                      f"weights are not spread over four devices: {used}")
        tally = Tally()
        answers[chips] = [
            generate(addr, tally, p, shape["new_tokens"] if i < 6 else 16,
                     f"{name} prompt {i}") for i, p in enumerate(prompts)]
        check(tally.non_200() == 0, f"non-200 answers: {tally.statuses}")
        say(f"{name} kernel paths (per trace): "
            f"{kernel_paths(get_metrics(addr))}")
        drain_to_exit(proc, addr, name, log_path)
    common = []
    for a, b in zip(answers[4], answers[1]):
        n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        common.append(n)
    say(f"greedy outputs, 4-chip tensor-parallel vs 1 chip: common prefix "
        f"per prompt {common} of {[len(a) for a in answers[4]]} tokens")
    first_differs = sum(1 for n in common if n == 0)
    check(first_differs * 2 <= len(common),
          f"the first token differs on {first_differs} of {len(common)} "
          "prompts: the tensor-parallel cell computes something else")
    return device


# --- main -------------------------------------------------------------------------


def preflight(out_dir: str) -> str:
    """The platform JAX comes up on, asked of a short child that exits
    (and so gives the chip back) before any cell starts."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import jax; print('BACKEND', jax.default_backend())"],
        env=child_env(out_dir), capture_output=True, text=True, timeout=300)
    line = next((ln for ln in p.stdout.splitlines()
                 if ln.startswith("BACKEND ")), None)
    check(p.returncode == 0 and line is not None,
          f"JAX did not come up: rc={p.returncode}\n{p.stderr[-1500:]}")
    return line.split()[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the tensor-parallel path and its "
                         "one-chip comparison (run by hand on four chips)")
    ap.add_argument("--model", choices=sorted(SHAPES), default="llama3-8b",
                    help="tiny = a rehearsal of the control flow on any "
                         "backend; never prints a pass")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args()
    rehearsal = args.model != "llama3-8b"
    os.makedirs(args.out, exist_ok=True)
    procs = Procs()
    device = None
    try:
        backend = preflight(args.out)
        say(f"JAX comes up on {backend!r}; model {args.model}"
            + (" (REHEARSAL: not a chip result)" if rehearsal else
               ", full width, all 32 layers"))
        check(rehearsal or backend == "tpu",
              f"JAX found no accelerator (backend {backend!r})")
        if args.chips == 4:
            device = phase_four_chips(procs, args.out, args.model, rehearsal)
        else:
            device, boot1, entries0 = phase_serve(
                procs, args.out, args.model, rehearsal)
            device2 = phase_daemon(procs, args.out, args.model, rehearsal,
                                   boot1, entries0)
            check(device2 == device, f"the daemon's cell reports another "
                                     f"device: {device2} vs {device}")
        check(rehearsal or (device["platform"] == "tpu"
                            and device["count"] == args.chips),
              f"device {device} is not {args.chips} TPU chip(s)")
    except Exception as e:  # noqa: BLE001 — the boundary: report, exit non-zero
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
        print(json.dumps({"ok": False, "error": str(e)[:300]}), flush=True)
        return 1
    finally:
        procs.stop_all()
    if rehearsal:
        say("rehearsal passed (this is not a chip result)")
        print(json.dumps({"ok": False, "rehearsal": "passed"}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
