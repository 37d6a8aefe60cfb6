"""Plain float32 reference of a dense GQA decoder (Mistral / Codestral block).

The published block: token embedding, then per layer RMSNorm -> grouped-query
attention with split-half rotary embeddings at the config's ``rope_theta`` ->
residual -> RMSNorm -> SwiGLU -> residual; final RMSNorm and an untied LM head.
No cache, no batching across requests, no kernels, no bf16: every matrix
product runs under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made. The
benchmark DEFINES the served weights: every matrix is a seeded Gaussian of
standard deviation ``fan_in ** -0.5`` stored as symmetric int8 with one float32
scale per output channel (per vocabulary row for the embedding), norms are
ones. ``draw_int8`` below is that definition; the reference multiplies the
int8 values by their float32 scales. The program's checkpoint-less int8 boot
draws the same values from the same seed (tests/bench pins the two against
each other at a tiny size), so agreement on the logits is agreement of the two
forward passes, not of two weight files.

Departures from the published models: random weights (no checkpoint can be
fetched here), weights-only int8 as the configuration states, and a served
context shorter than the published 32768 positions (no sliding window is in
either config).

``precision`` selects the lower-precision controls the correctness limit has
to reject (never used by a benchmark run):
  "a8"  int8 activations into every matrix product and an int8 KV, the step
        below the configuration's bf16 activations;
  "w4"  int4 weights, the step below its int8 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Order in which the seed's nine sub-keys are spent.
MATRICES = ("embed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "lm_head")
Q_BLOCK = 256      # query rows attended at once (bounds the score matrix)
HEAD_ROWS = 128    # LM-head rows are padded to a multiple of this


def dims(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return {"H": h, "I": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "NH": nh, "NKV": nkv, "D": d}


def _sym_quant(w, axis, levels):
    a = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(a / levels, 1e-12)
    return jnp.round(w / s), s


def draw_int8(key, shape, fan_in, axis):
    """The weight definition: (int8 values, float32 scales kept along
    ``axis``) of a seeded Gaussian matrix."""
    w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    q, s = _sym_quant(w, axis, 127.0)
    return q.astype(jnp.int8), s


def _weight(key, shape, fan_in, axis, precision):
    q, s = draw_int8(key, shape, fan_in, axis)
    w = q.astype(jnp.float32) * s
    if precision == "w4":
        q4, s4 = _sym_quant(w, axis, 7.0)
        w = q4 * s4
    return w


def _act(x, precision):
    """What enters a matrix product: float32, or per-row int8 for "a8"."""
    if precision != "a8":
        return x
    q, s = _sym_quant(x, -1, 127.0)
    return q * s


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """x [T, heads, D]; split-half rotation (the published convention)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA over one sequence. q [T, NH, D], k/v [T, NKV, D]."""
    T, NH, D = q.shape
    NKV = k.shape[1]
    qg = q.reshape(T // Q_BLOCK, Q_BLOCK, NKV, NH // NKV, D)
    kv_pos = jnp.arange(T)

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) * D ** -0.5
        q_pos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(kv_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (qg, jnp.arange(T // Q_BLOCK) * Q_BLOCK))
    return out.reshape(T, NH * D)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _embed(key, tokens, cfg_t, precision):
    c = dict(cfg_t)
    w = _weight(key, (c["V"], c["H"]), c["H"], 1, precision)
    return jnp.take(w, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision", "spread"))
def _layer(keys, x, cfg_t, precision, spread=False):
    """One decoder block over x [B, T, H]; ``keys`` holds this layer's key
    of each of the seven matrices, in MATRICES order."""
    c = dict(cfg_t)
    H, I, NH, NKV, D = c["H"], c["I"], c["NH"], c["NKV"], c["D"]
    with jax.default_matmul_precision("highest"):
        wq = _weight(keys[0], (H, NH * D), H, 0, precision)
        wk = _weight(keys[1], (H, NKV * D), H, 0, precision)
        wv = _weight(keys[2], (H, NKV * D), H, 0, precision)
        wo = _weight(keys[3], (NH * D, H), NH * D, 0, precision)
        wg = _weight(keys[4], (H, I), H, 0, precision)
        wu = _weight(keys[5], (H, I), H, 0, precision)
        wd = _weight(keys[6], (I, H), I, 0, precision)
        T = x.shape[1]
        pos = jnp.arange(T)

        def one(xs):
            h = _act(_rms_norm(xs, c["eps"]), precision)
            q = _rope((h @ wq).reshape(T, NH, D), pos, c["theta"])
            k = _rope((h @ wk).reshape(T, NKV, D), pos, c["theta"])
            v = (h @ wv).reshape(T, NKV, D)
            if precision == "a8":
                k, v = _act(k, precision), _act(v, precision)
            xs = xs + _act(_attention(q, k, v), precision) @ wo
            h = _act(_rms_norm(xs, c["eps"]), precision)
            gated = jax.nn.silu(h @ wg) * (h @ wu)
            return xs + _act(gated, precision) @ wd

        # One sequence at a time on one device; where the caller spread the
        # sequences over several devices, each device takes its own.
        if spread:
            return jax.vmap(one)(x)
        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _head(key, x, cfg_t, precision):
    c = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        w = _weight(key, (c["H"], c["V"]), c["H"], 0, precision)
        return _act(_rms_norm(x, c["eps"]), precision) @ w


def logits_at(cfg: dict, seed: int, sequences: list[np.ndarray],
              positions: list[np.ndarray], pad_to: int,
              precision: str = "f32") -> list[np.ndarray]:
    """Full forward of each token sequence; returns, per sequence, the float32
    logits [len(positions[i]), V] at the given positions (row p holds the
    distribution of token p + 1). Sequences are padded to ``pad_to`` rows (a
    multiple of Q_BLOCK) so that every run of a cell compiles the same three
    programs; causal attention keeps the padding out of every real row."""
    d = dims(cfg)
    assert pad_to % Q_BLOCK == 0 and max(len(s) for s in sequences) <= pad_to
    cfg_t = tuple(sorted({**d, "eps": float(cfg["rms_norm_eps"]),
                          "theta": float(cfg["rope_theta"])}.items()))
    keys = jax.random.split(jax.random.key(int(seed)), len(MATRICES))
    tokens = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    x = _embed(keys[0], jnp.asarray(tokens), cfg_t, precision)
    devices = jax.devices()
    spread = len(devices) > 1 and len(sequences) % len(devices) == 0
    if spread:
        # A cell on several chips: one share of the sequences to each chip
        # (every chip draws the same weights itself), to shorten the check.
        mesh = jax.sharding.Mesh(np.array(devices), ("seq",))
        x = jax.device_put(x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("seq")))
    per_layer = jnp.stack([jax.random.split(keys[m], d["L"])
                           for m in range(1, 8)], axis=1)      # [L, 7]
    for layer in range(d["L"]):
        x = _layer(per_layer[layer], x, cfg_t, precision, spread)
    out = []
    for i, pos in enumerate(positions):
        padded = -(-len(pos) // HEAD_ROWS) * HEAD_ROWS   # few head shapes
        idx = np.zeros(padded, np.int32)
        idx[:len(pos)] = pos
        logits = _head(keys[8], x[i][idx], cfg_t, precision)
        out.append(np.asarray(logits[:len(pos)]))
    return out
