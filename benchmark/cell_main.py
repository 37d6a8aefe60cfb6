"""The process that holds the chip(s): boots the system under test, serves it
over the cell's own HTTP server, and afterwards runs the plain reference.

run.py starts this file as a child and never imports JAX itself. The two talk
in JSON lines: this process prints ``BENCH <json>`` records on its standard
output and reads one command per line on its standard input:

  {"cmd": "check", "requests": [...]}   -> read the peak memory, stop serving,
                                           free the program's arrays, run the
                                           reference over the served sequences
  {"cmd": "exit"}

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


def llama_config(config: dict):
    """The configuration file's published sizes as the program's config."""
    import jax.numpy as jnp

    from kukeon_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=config["max_position_embeddings"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=getattr(jnp, config["torch_dtype"]))


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where JAX reports none)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CellHost:
    """One served cell: the normal ``ServingCell`` behind its own handler."""

    def __init__(self, config: dict, seed: int, pkg_dir: str = plugins.HERE):
        self.config, self.seed, self.pkg_dir = config, int(seed), pkg_dir
        self.cell = self.server = self._thread = None

    def boot(self, warm_prompt_len: int) -> dict:
        from http.server import ThreadingHTTPServer

        from kukeon_tpu.runtime import serving_cell as sc

        levers = sorted(k for k in os.environ if k.startswith("KUKEON_")
                        and k not in HARMLESS_ENV)
        if levers:
            raise SystemExit(f"benchmark: unset {levers}: a cell runs at the "
                             "levers its configuration file states")
        s = self.config["serving"]
        name = self.config["name"]
        cfg = llama_config(self.config)
        sc.MODELS[name] = lambda: cfg
        cell = sc.ServingCell(
            name, num_slots=s["num_slots"], max_seq_len=s["max_seq_len"],
            checkpoint=None, dtype=s["dtype"], seed=self.seed,
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
        return {
            "port": server.server_address[1],
            "boot_phases_s": {k: round(v, 3) for k, v in phases.items()},
            "levers": {
                "prefill_buckets": list(eng.prefill_buckets),
                "decode_chunk": eng.decode_chunk, "paged": eng.paged,
                "kv_cache_int8": eng.kv_cache_int8, "slots": eng.num_slots,
                "rows": eng.max_seq_len, "int8_pallas": eng.cfg.int8_pallas,
                "kv_shard": eng.kv_shard, "mesh_chips": int(eng.mesh.size),
                "prefix_entries": eng._prefix_cache_size,
                "prefix_bytes": eng._prefix_cache_bytes,
                "max_pending": eng.max_pending, "weights_seed": self.seed,
            },
        }

    def stop_and_free(self) -> None:
        """Stop serving and delete every array the program holds, so that the
        reference has the device to itself."""
        import jax

        eng = self.cell.engine
        self.server.shutdown()
        self.server.server_close()
        self._thread.join(timeout=30)
        eng.stop()
        held = [eng.params, eng.state,
                [(e.kv_k, e.kv_v) for e in eng._prefix_cache.values()]]
        eng._prefix_cache.clear()
        for leaf in jax.tree.leaves(held):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        self.cell = self.server = None
        gc.collect()

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
            peak = peak_bytes()
            self.stop_and_free()
            out = self.check(msg["requests"], msg["pad_to"],
                             tuple(msg.get("controls", ())))
            return {**out, "memory_peak_bytes": peak}
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
    device = device_gate(config["serving"]["chips"])
    host = CellHost(config, args.seed,
                    os.path.dirname(os.path.dirname(os.path.abspath(args.config))))
    emit("ready", device=device, **host.boot(args.warm_prompt_len))
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["cmd"] == "exit":
            break
        emit("reply", cmd=msg["cmd"], **host.command(msg))
    if host.cell is not None:
        host.stop_and_free()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)     # the runtime's own teardown can abort with threads alive


if __name__ == "__main__":
    sys.exit(main())
