"""Checkpoint tooling: synthesize HF-layout checkpoints and save/load the
kukeon int8 quantized format.

Two jobs, both in service of serving a full-size model (BASELINE north star:
Llama-3-8B serving on v5e):

1. **Synthesis** — this environment has no network egress, so "load a real
   8B checkpoint" is exercised against a synthesized one: the exact HF hub
   layout (config.json + sharded ``model-*.safetensors`` +
   ``model.safetensors.index.json`` + tokenizer.json) with random weights at
   the real shapes/dtypes. Every byte of the serving path — shard streaming,
   name mapping, transposes, tokenizer.json loading — is the code a real
   download would hit (reference test strategy: fakes with real protocol,
   SURVEY.md §4).

2. **Quantized format** — cold-start (<90s target) cannot afford
   re-quantizing 16 GB of bf16 on every model-cell boot. ``save_quantized``
   persists the int8 {"q","s"} pytree as safetensors (~½ the bytes, zero
   quantization work at load); ``load_quantized`` streams it back as numpy
   leaves ready for device_put. ``kukeon_quant.json`` carries the
   LlamaConfig so the server never hand-syncs shapes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue
import struct
import threading
import time
from typing import Any, Callable, Iterator

import numpy as np

from kukeon_tpu import faults
from kukeon_tpu.models.llama import LlamaConfig

QUANT_MANIFEST = "kukeon_quant.json"

_CFG_FIELDS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_layers",
    "num_heads", "num_kv_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "max_seq_len", "tie_embeddings",
)


def _cfg_to_json(cfg: LlamaConfig) -> dict:
    return {f: getattr(cfg, f) for f in _CFG_FIELDS}


def _cfg_from_json(d: dict) -> LlamaConfig:
    return LlamaConfig(**{f: d[f] for f in _CFG_FIELDS if f in d})


# --- HF-layout synthesis ------------------------------------------------------

def write_hf_config(path: str, cfg: LlamaConfig) -> None:
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "max_position_embeddings": cfg.max_seq_len,
            "tie_word_embeddings": cfg.tie_embeddings,
            "torch_dtype": "float16",
        }, f, indent=1)


def write_tokenizer_json(path: str) -> None:
    """A real (HF ``tokenizers``-format) byte-level BPE with Llama-3 special
    tokens — small trained vocab, but byte-complete so any text round-trips.
    Exercises the exact HFTokenizer path a downloaded tokenizer.json would."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tk = Tokenizer(models.BPE(unk_token=None))
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=2048,
        special_tokens=["<|begin_of_text|>", "<|end_of_text|>", "<|eot_id|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    corpus = [
        "def main(argv):\n    return run(argv)\n",
        "the quick brown fox jumps over the lazy dog",
        "kukeon serves agent sessions on tpu slices with scoped secrets",
        "import jax\nimport numpy as np\n",
    ] * 64
    tk.train_from_iterator(corpus, trainer)
    tk.save(os.path.join(path, "tokenizer.json"))


def synthesize_hf_checkpoint(
    path: str,
    cfg: LlamaConfig,
    *,
    seed: int = 0,
    dtype: Any = np.float16,
    max_shard_bytes: int = 4 << 30,
    tokenizer: bool = True,
) -> str:
    """Write a random-weights checkpoint at ``cfg``'s shapes in the HF hub
    layout (sharded safetensors + index + config.json [+ tokenizer.json]).

    Weights are streamed to shards one tensor at a time — an 8B checkpoint
    (~16 GB f16) never holds more than one tensor in memory. Idempotent:
    returns immediately if the directory already has an index/config.
    """
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    if os.path.exists(os.path.join(path, "config.json")) and (
        os.path.exists(os.path.join(path, "model.safetensors.index.json"))
        or os.path.exists(os.path.join(path, "model.safetensors"))
    ):
        return path

    rng = np.random.default_rng(seed)
    c = cfg
    H, I, V = c.hidden_size, c.intermediate_size, c.vocab_size

    def tensor_specs():
        yield "model.embed_tokens.weight", (V, H), H
        for i in range(c.num_layers):
            p = f"model.layers.{i}."
            yield p + "input_layernorm.weight", (H,), None
            yield p + "self_attn.q_proj.weight", (c.q_dim, H), H
            yield p + "self_attn.k_proj.weight", (c.kv_dim, H), H
            yield p + "self_attn.v_proj.weight", (c.kv_dim, H), H
            yield p + "self_attn.o_proj.weight", (H, c.q_dim), c.q_dim
            yield p + "post_attention_layernorm.weight", (H,), None
            yield p + "mlp.gate_proj.weight", (I, H), H
            yield p + "mlp.up_proj.weight", (I, H), H
            yield p + "mlp.down_proj.weight", (H, I), I
        yield "model.norm.weight", (H,), None
        if not c.tie_embeddings:
            yield "lm_head.weight", (V, H), H

    def make(shape, fan_in):
        if fan_in is None:
            return np.ones(shape, dtype)          # norm scales
        w = rng.standard_normal(shape, np.float32)
        w *= fan_in ** -0.5
        return w.astype(dtype)

    weight_map: dict[str, str] = {}
    shard: dict[str, np.ndarray] = {}
    shard_bytes = 0
    shard_names: list[str] = []

    def flush():
        nonlocal shard, shard_bytes
        if not shard:
            return
        name = f"model-part-{len(shard_names):05d}.safetensors"
        save_file(shard, os.path.join(path, name))
        shard_names.append(name)
        for n in shard:
            weight_map[n] = name
        shard = {}
        shard_bytes = 0

    for name, shape, fan_in in tensor_specs():
        t = make(shape, fan_in)
        if shard_bytes + t.nbytes > max_shard_bytes:
            flush()
        shard[name] = t
        shard_bytes += t.nbytes
    flush()

    # Rename to the canonical HF n-of-m scheme now that m is known.
    total = len(shard_names)
    final_map: dict[str, str] = {}
    renames: dict[str, str] = {}
    for idx, name in enumerate(shard_names):
        final = f"model-{idx + 1:05d}-of-{total:05d}.safetensors"
        renames[name] = final
        os.rename(os.path.join(path, name), os.path.join(path, final))
    for n, shard_name in weight_map.items():
        final_map[n] = renames[shard_name]
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"weight_map": final_map}, f)
    write_hf_config(path, cfg)
    if tokenizer:
        write_tokenizer_json(path)
    return path


# --- kukeon int8 quantized checkpoint ----------------------------------------

def _flatten_quant(params: dict) -> dict[str, np.ndarray]:
    flat: dict[str, np.ndarray] = {}

    def walk(prefix: str, node):
        if isinstance(node, dict):
            if "q" in node and "s" in node and len(node) == 2:
                flat[prefix + ".q"] = np.asarray(node["q"])
                flat[prefix + ".s"] = np.asarray(node["s"])
            else:
                for k, v in node.items():
                    walk(f"{prefix}.{k}" if prefix else k, v)
        else:
            flat[prefix] = np.asarray(node)

    walk("", params)
    return flat


def _unflatten_quant(flat: dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for name, t in flat.items():
        parts = name.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = t
    return tree


def save_quantized(path: str, params: dict, cfg: LlamaConfig) -> str:
    """Persist an int8 {"q","s"} pytree as safetensors + manifest."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    flat = _flatten_quant(params)
    # ml_dtypes bfloat16 isn't a safetensors-numpy dtype; norms store as f32.
    flat = {
        k: (v.astype(np.float32) if v.dtype not in (np.dtype(np.int8),
                                                    np.dtype(np.float32),
                                                    np.dtype(np.float16)) else v)
        for k, v in flat.items()
    }
    save_file(flat, os.path.join(path, "model.quant.safetensors"))
    with open(os.path.join(path, QUANT_MANIFEST), "w") as f:
        json.dump({"format": "kukeon-int8-v1", "config": _cfg_to_json(cfg)}, f)
    return path


def is_quantized_checkpoint(path: str) -> bool:
    return os.path.exists(os.path.join(path, QUANT_MANIFEST))


def load_quantized(path: str, dtype=None) -> tuple[dict, LlamaConfig]:
    """Load the int8 pytree back (numpy leaves; norms cast to ``dtype`` or
    the config's activation dtype)."""
    import jax.numpy as jnp
    from safetensors import safe_open

    with open(os.path.join(path, QUANT_MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != "kukeon-int8-v1":
        raise ValueError(f"unknown quantized checkpoint format in {path}")
    cfg = _cfg_from_json(manifest["config"])
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    ndtype = np.dtype(cfg.dtype)
    flat: dict[str, np.ndarray] = {}
    with safe_open(os.path.join(path, "model.quant.safetensors"),
                   framework="numpy") as f:
        for name in f.keys():
            t = f.get_tensor(name)
            if t.dtype == np.float32 and not name.endswith(".s"):
                t = t.astype(ndtype)   # norm scales follow activation dtype
            flat[name] = t
    params = _unflatten_quant(flat)
    # jnp import kept above so callers on fresh processes pay it here, not
    # at first forward.
    del jnp
    return params, cfg


# --- streamed (tensor-granular) checkpoint pipeline ---------------------------

class CheckpointStreamError(RuntimeError):
    """A reader thread died mid-stream (I/O error, decode error, or the
    armed ``checkpoint.stream`` fault point). The consumer re-raises this
    so a boot can fail CLEAN — a half-loaded engine must never flip
    /readyz."""


class TensorSpec:
    """Shape+dtype stand-in for one param leaf, parsed from the checkpoint
    manifest before any tensor byte is read. Duck-types the subset of the
    array interface the sharding planner (``parallel.sharding``) and
    ``jax.ShapeDtypeStruct`` construction need — deliberately NOT a jax
    type, so building the abstract tree costs no device work."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape: tuple[int, ...], dtype) -> None:
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        n = self.dtype.itemsize
        for d in self.shape:
            n *= d
        return n

    def __repr__(self) -> str:
        return f"TensorSpec(shape={self.shape}, dtype={self.dtype})"


# safetensors header dtype strings -> numpy dtypes. BF16 resolves lazily
# (ml_dtypes registers it with numpy via the jax import chain).
_ST_DTYPES = {
    "F64": "float64", "F32": "float32", "F16": "float16", "BF16": "bfloat16",
    "I64": "int64", "I32": "int32", "I16": "int16", "I8": "int8",
    "U8": "uint8", "BOOL": "bool",
}


def read_safetensors_header(path: str) -> dict[str, TensorSpec]:
    """tensor name -> TensorSpec from a safetensors file's JSON header —
    the whole-checkpoint manifest for the cost of one small read (the
    8-byte length prefix plus the header itself; zero tensor bytes)."""
    with open(path, "rb") as f:
        (n,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(n))
    out: dict[str, TensorSpec] = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        out[name] = TensorSpec(tuple(meta["shape"]),
                               np.dtype(_ST_DTYPES[meta["dtype"]]))
    return out


def _walk_tree(node, prefix: tuple[str, ...] = ()) -> Iterator[tuple[tuple[str, ...], Any]]:
    """(path tuple, leaf) pairs of a nested-dict param tree (quantized
    {"q","s"} dicts are interior nodes here: their arrays are the leaves)."""
    if isinstance(node, dict):
        for k in node:
            yield from _walk_tree(node[k], prefix + (k,))
    else:
        yield prefix, node


class CheckpointStream:
    """Bounded-buffer tensor-granular checkpoint reader.

    ``jobs`` is a list of zero-arg callables, each returning
    ``(leaves, disk_s, cast_s)`` where ``leaves`` is a list of
    ``(path tuple, np.ndarray)`` pairs ready for device_put. ``threads``
    reader threads drain the job list concurrently (tensor i+1's disk read
    overlaps tensor i's upload on the consumer side) and push results
    through a bounded queue, so host memory holds at most
    ``buffer + threads`` tensors no matter how far the disk runs ahead of
    the device link.

    The consumer iterates ``(path, array)`` pairs until every leaf of
    :attr:`abstract_params` arrived; a reader error (or the armed
    ``checkpoint.stream`` fault point) surfaces as
    :class:`CheckpointStreamError` on the consuming thread — fail-clean is
    the contract, never a silent half-tree.

    :attr:`stats` accumulates ``disk_s`` / ``cast_s`` / ``bytes`` /
    ``tensors`` under a lock; scrape it via :meth:`stat_snapshot`.
    """

    def __init__(self, abstract_params: dict, cfg, jobs: list[Callable],
                 *, threads: int = 4, buffer: int = 16):
        from kukeon_tpu import sanitize

        self.abstract_params = abstract_params
        self.cfg = cfg
        self.total_leaves = sum(1 for _ in _walk_tree(abstract_params))
        self._jobs = list(jobs)
        self._jobs_lock = sanitize.lock("CheckpointStream._jobs_lock")
        self._stats_lock = sanitize.lock("CheckpointStream._stats_lock")
        self.stats = {"disk_s": 0.0, "cast_s": 0.0,
                      "bytes": 0, "tensors": 0}        # guarded-by: _stats_lock
        self._q: queue.Queue = queue.Queue(maxsize=max(1, buffer))
        self._closed = sanitize.event("CheckpointStream._closed")
        self._threads = [
            threading.Thread(target=self._reader, daemon=True,
                             name=f"ckpt-stream-{i}")
            for i in range(max(1, min(threads, len(self._jobs) or 1)))
        ]
        for t in self._threads:
            t.start()

    # --- reader side --------------------------------------------------------

    def _reader(self) -> None:
        while not self._closed.is_set():
            with self._jobs_lock:
                if not self._jobs:
                    return
                job = self._jobs.pop(0)
            try:
                faults.maybe_fail("checkpoint.stream")
                leaves, disk_s, cast_s = job()
            except BaseException as e:  # noqa: BLE001 — surfaced to the consumer
                self._put(("err", CheckpointStreamError(
                    f"checkpoint stream reader failed: "
                    f"{type(e).__name__}: {e}"), e))
                return
            nbytes = sum(arr.nbytes for _, arr in leaves)
            with self._stats_lock:
                self.stats["disk_s"] += disk_s
                self.stats["cast_s"] += cast_s
                self.stats["bytes"] += nbytes
                self.stats["tensors"] += len(leaves)
            for path, arr in leaves:
                if not self._put(("leaf", path, arr)):
                    return

    def _put(self, item) -> bool:
        """Bounded put that gives up once the stream is closed (a consumer
        that errored out must not leave readers blocked forever)."""
        while not self._closed.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    # --- consumer side ------------------------------------------------------

    def __iter__(self) -> Iterator[tuple[tuple[str, ...], np.ndarray]]:
        remaining = self.total_leaves
        try:
            while remaining:
                item = self._q.get()
                if item[0] == "err":
                    raise item[1] from item[2]
                yield item[1], item[2]
                remaining -= 1
        finally:
            self.close()

    def close(self) -> None:
        """Stop the readers (idempotent). Iteration closes on completion
        and on error; an engine tearing down early must call this too."""
        self._closed.set()

    def stat_snapshot(self) -> dict:
        with self._stats_lock:
            return dict(self.stats)


def _timed_get(get: Callable[[], np.ndarray]) -> tuple[np.ndarray, float]:
    t0 = time.monotonic()
    out = get()
    return out, time.monotonic() - t0


def stream_quantized(path: str, dtype=None, *, threads: int = 4,
                     buffer: int = 16) -> CheckpointStream:
    """Streaming twin of :func:`load_quantized`: the abstract param tree
    and config come from the manifest + safetensors header alone (so
    ``precompile()`` can start before any tensor byte is read), then
    reader threads walk the file tensor-by-tensor, casting norms to the
    activation dtype on the host. Leaf values and tree structure are
    byte-identical to the materialized loader's."""
    with open(os.path.join(path, QUANT_MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != "kukeon-int8-v1":
        raise ValueError(f"unknown quantized checkpoint format in {path}")
    cfg = _cfg_from_json(manifest["config"])
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    ndtype = np.dtype(cfg.dtype)
    st_path = os.path.join(path, "model.quant.safetensors")
    header = read_safetensors_header(st_path)

    abstract_flat = {
        name: (TensorSpec(spec.shape, ndtype)
               if spec.dtype == np.dtype(np.float32)
               and not name.endswith(".s") else spec)
        for name, spec in header.items()
    }
    abstract = _unflatten_quant(abstract_flat)  # type: ignore[arg-type]

    from safetensors import safe_open

    tls = threading.local()

    def _handle():
        f = getattr(tls, "f", None)
        if f is None:
            f = tls.f = safe_open(st_path, framework="numpy")
        return f

    def make_job(name: str):
        want = abstract_flat[name].dtype

        def job():
            t, disk_s = _timed_get(lambda: _handle().get_tensor(name))
            t0 = time.monotonic()
            if t.dtype != want:
                t = t.astype(want)
            cast_s = time.monotonic() - t0
            return [(tuple(name.split(".")), t)], disk_s, cast_s

        return job

    jobs = [make_job(name) for name in header]
    return CheckpointStream(abstract, cfg, jobs,
                            threads=threads, buffer=buffer)
