"""The process that holds the chip(s): boots the system under test, serves it
over the cell's own HTTP server, and afterwards runs the plain reference.

run.py starts this file as a child and never imports JAX itself. The two talk
in JSON lines: this process prints ``BENCH <json>`` records on its standard
output and reads one command per line on its standard input:

  {"cmd": "check", "requests": [...]}   -> read the peak memory, stop serving,
                                           free the program's arrays, run the
                                           reference over the served sequences
  {"cmd": "exit"}

Nothing here names a model family: the configuration file does
(``"reference": "<family>"``), and ``launchers/<family>.py`` enters the
configuration in the program's own registry before ``CellHost.boot`` builds the
normal ``ServingCell`` from it.

Nothing here falls back to the CPU: ``device_gate`` ends the process unless JAX
reports a TPU with at least the chips the cell asks for. Tests drive ``CellHost``
in their own process and never call the gate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


from benchmark import plugins  # noqa: E402

# KUKEON_* variables that no code of the serving cell reads as a lever: the
# capture directory this file sets, and the runtime's network switch.
HARMLESS_ENV = ("KUKEON_PROFILE_DIR", "KUKEON_NET_ENFORCE")


def emit(kind: str, **fields) -> None:
    print("BENCH " + json.dumps({"kind": kind, **fields}), flush=True)


def device_gate(chips: int) -> dict:
    """The device as JAX reports it, or the end of the run."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] < chips:
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s); JAX found platform "
            f"{found['platform']!r} ({found['kind']}) with {found['count']} "
            f"device(s). No result.")
    return found


def device_bytes(stat: str) -> int:
    """``peak_bytes_in_use`` or ``bytes_in_use`` of the fullest device (0
    where JAX reports no memory statistics, as on the CPU)."""
    import jax

    return int(max((d.memory_stats() or {}).get(stat, 0)
                   for d in jax.devices()))


class CellHost:
    """One served cell: the normal ``ServingCell`` behind its own handler."""

    def __init__(self, config: dict, seed: int, pkg_dir: str = plugins.HERE):
        self.config, self.seed, self.pkg_dir = config, int(seed), pkg_dir
        self.cell = self.server = self._thread = None
        self._not_mine: list = []

    def boot(self, warm_prompt_len: int) -> dict:
        from http.server import ThreadingHTTPServer

        import jax

        from kukeon_tpu.runtime import serving_cell as sc

        levers = sorted(k for k in os.environ if k.startswith("KUKEON_")
                        and k not in HARMLESS_ENV)
        if levers:
            raise SystemExit(f"benchmark: unset {levers}: a cell runs at the "
                             "levers its configuration file states")
        # Arrays that were alive before this cell (none in run.py's child; a
        # test process has other tests'): stop_and_free leaves them alone.
        self._not_mine = jax.live_arrays()
        s = self.config["serving"]
        plugins.load("launchers", self.config["reference"],
                     self.pkg_dir).register(self.config)
        cell = sc.ServingCell(
            self.config["name"], num_slots=s["num_slots"],
            max_seq_len=s["max_seq_len"], checkpoint=None, dtype=s["dtype"],
            seed=self.seed,
            kv_cache_int8=s["kv_cache_int8"], decode_chunk=s["decode_chunk"],
            kv_page_tokens=s["kv_page_tokens"], max_pending=s["max_pending"],
            deadline_s=s["deadline_s"], chips=s["chips"])
        eng = cell.engine
        if eng.tune is not None:
            raise SystemExit("benchmark: the engine took levers from a tune "
                             "file; a cell runs at its configuration file's")
        cell.warmup(warm_prompt_len)
        eng.start()
        server = ThreadingHTTPServer(("127.0.0.1", 0), sc.make_handler(cell))
        cell.on_drained = server.shutdown
        phases = cell.finish_boot()
        cell.mark_ready()
        self.cell, self.server = cell, server
        self._thread = threading.Thread(target=server.serve_forever,
                                        daemon=True, name="bench-http")
        self._thread.start()
        # A lever that only some families' configs have is recorded where
        # the program's config has it and left out where it has not.
        pallas = ({"int8_pallas": eng.cfg.int8_pallas}
                  if hasattr(eng.cfg, "int8_pallas") else {})
        return {
            "port": server.server_address[1],
            "boot_phases_s": {k: round(v, 3) for k, v in phases.items()},
            "levers": {
                "prefill_buckets": list(eng.prefill_buckets),
                "decode_chunk": eng.decode_chunk, "paged": eng.paged,
                "kv_cache_int8": eng.kv_cache_int8, "slots": eng.num_slots,
                "rows": eng.max_seq_len, **pallas,
                "kv_shard": eng.kv_shard, "mesh_chips": int(eng.mesh.size),
                "prefix_entries": eng._prefix_cache_size,
                "prefix_bytes": eng._prefix_cache_bytes,
                "max_pending": eng.max_pending, "weights_seed": self.seed,
            },
        }

    def stop_and_free(self) -> dict:
        """Stop serving and delete every array that came to live on the device
        since ``boot`` began, whoever holds it (weights, decode state, prefix
        store, a family's own rings or tables), so that the reference has the
        device to itself. Returns what was freed and the bytes still in use
        on the fullest device afterwards."""
        import jax

        self.server.shutdown()
        self.server.server_close()
        self._thread.join(timeout=30)
        self.cell.engine.stop()
        kept = {id(a) for a in self._not_mine}
        mine = [a for a in jax.live_arrays()
                if id(a) not in kept and not a.is_deleted()]
        n, freed = len(mine), sum(a.nbytes for a in mine)
        for a in mine:
            a.delete()
        del mine
        self.cell = self.server = None
        self._not_mine = []
        gc.collect()
        return {"freed_arrays": n, "freed_bytes": int(freed),
                "bytes_in_use_under_reference": device_bytes("bytes_in_use")}

    def check(self, requests: list[dict], pad_to: int,
              controls: tuple = ()) -> dict:
        """Reference logits over each served sequence; the gap by which a
        served token's logit lies below the reference's best. A request is
        ``{"sequence": tokens, "spans": [[first, count], ...]}``: the tokens
        at first + 1 .. first + count were served, each predicted from the
        position before it (a session's last turn carries the answers of its
        earlier turns inside its prompt). For each lower precision in
        ``controls`` (never asked for by a benchmark run), the same gap of
        the token that precision puts first."""
        import numpy as np

        ref = plugins.load("reference", self.config["reference"],
                           self.pkg_dir)
        seqs = [np.asarray(r["sequence"], np.int32) for r in requests]
        positions = [np.concatenate([np.arange(f, f + c) for f, c in
                                     r["spans"]]) for r in requests]
        served = [s[p + 1] for s, p in zip(seqs, positions)]

        def gaps_of(tokens_each):
            return np.concatenate([
                lg.max(-1) - lg[np.arange(len(t)), np.asarray(t)]
                for lg, t in zip(logits, tokens_each)])

        t0 = time.monotonic()
        logits = ref.logits_at(self.config, self.seed, seqs, positions, pad_to)
        gaps = gaps_of(served)
        out = {"requests": sum(len(r["spans"]) for r in requests),
               "sequences": len(requests), "tokens": int(gaps.size),
               "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
               "flipped": int((gaps > 0).sum()),
               "logit_std": float(np.mean([lg.std() for lg in logits])),
               "reference_s": round(time.monotonic() - t0, 3)}
        for precision in controls:
            lower = ref.logits_at(self.config, self.seed, seqs, positions,
                                  pad_to, precision=precision)
            g = gaps_of([lg.argmax(-1) for lg in lower])
            out["control_" + precision] = {
                "gap_max": float(g.max()), "gap_mean": float(g.mean()),
                "flipped": int((g > 0).sum())}
        return out

    def command(self, msg: dict) -> dict:
        cmd = msg["cmd"]
        if cmd == "check":
            peak = device_bytes("peak_bytes_in_use")
            freed = self.stop_and_free()
            out = self.check(msg["requests"], msg["pad_to"],
                             tuple(msg.get("controls", ())))
            return {**out, **freed, "memory_peak_bytes": peak}
        raise ValueError(f"unknown command {cmd!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--warm-prompt-len", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    os.environ["KUKEON_PROFILE_DIR"] = os.path.join(args.run_dir, "profiles")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))
    try:
        device = device_gate(config["serving"]["chips"])
        host = CellHost(config, args.seed, os.path.dirname(
            os.path.dirname(os.path.abspath(args.config))))
        emit("ready", device=device, **host.boot(args.warm_prompt_len))
        for line in sys.stdin:
            msg = json.loads(line)
            if msg["cmd"] == "exit":
                break
            emit("reply", cmd=msg["cmd"], **host.command(msg))
    except BaseException as e:      # told to the parent, then raised as it was
        emit("failed", error=plugins.one_line(e, 400))
        raise
    if host.cell is not None:
        host.stop_and_free()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)     # the runtime's own teardown can abort with threads alive


if __name__ == "__main__":
    sys.exit(main())
