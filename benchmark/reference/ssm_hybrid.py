"""Plain float32 reference of a decoder of state-space layers with an attention
layer among them (the ``jamba`` block of ai21labs/AI21-Jamba2-3B: Mamba-1
mixers, two attention layers, a dense SwiGLU in every layer since
``num_experts`` is 1). ``x`` is ``[T, H]``, every norm an RMSNorm with a gain.

    x0 = E[tokens]                                  no scaling, no positions
    x = x + Mix_l(N1_l x);  x = x + MLP_l(N2_l x);  logits = N_f(x) E^T  (tied)
    MLP(h)  = (silu(h Wg) * (h Wu)) Wd
    Attn(h) (layer i with i % attn_layer_period == attn_layer_offset):
              q = h Wq [NH x D], k = h Wk, v = h Wv [NKV x D],
              causal softmax(q k^T / sqrt(D)) v, then Wo; no rotary, no bias
    Mamba(h): [u, z] = h W_in
              c_t = silu(b_c + sum_j w_c[:, j] u_{t-(K-1)+j})    u_t = 0, t < 0
              [r, B, C]_t = c_t W_x;  r = N_dt(r), B = N_b(B), C = N_c(C)
              d_t = softplus(r_t W_dt + b_dt);  A = -exp(A_log)
              H_t = exp(d_t[:, None] * A) * H_{t-1} + (d_t * c_t)[:, None] * B_t
              y_t = H_t C_t + D * c_t;  out = (y * silu(z)) W_out       H_-1 = 0

No cache, no state handed on, no chunks, no kernel, no batching across
requests, no bf16 arithmetic: every product runs under
``jax.default_matmul_precision("highest")``, one sequence at a time, the
recurrence as a ``lax.scan`` over single tokens with its state in float32,
attention in query blocks.

It imports nothing of the program. The benchmark DEFINES the served weights:
a drawn leaf is seeded under the key folded from (seed, the leaf's index in
``LEAVES``, the layer's number in the model). A matrix is a Gaussian of
standard deviation ``fan_in ** -0.5`` rounded to bfloat16 as the
configuration states; norm gains ``1 + 0.1 g``; the convolution's bias
``0.1 g``. The scan's own leaves follow the Mamba paper's initialisation, which
leaves the state a LONG memory (a channel's decay a step runs from 0.999 to
0.2), so that a lost or stale state shows in the logits: ``A_log = log(1 ..
d_state)`` in every channel and ``D = 1`` (S4D-real, float32), ``b_dt`` the
inverse softplus of a step size log-uniform in [1e-3, 1e-1] (float32), ``W_dt``
a tenth of a matrix's deviation. The program's checkpoint-less boot follows the
same recipe (``tests/bench`` pins the two at a tiny size).

``precision`` selects the lower-precision controls the limits have to reject
(never used by a benchmark run): "a8" int8 activations into every matrix
product, an int8 KV AND the scan state rounded to bfloat16 after every step,
the step below what the configuration states (bf16 activations, a float32
state); "w4" int4 weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("embed", "final_norm", "norm1", "norm2", "w_gate", "w_up", "w_down",
          "wq", "wk", "wv", "wo", "w_in", "conv_w", "conv_b", "w_x", "dt_norm",
          "b_norm", "c_norm", "w_dt", "b_dt", "w_out")
GAIN_STD = 0.1
CONV_BIAS_STD = 0.1
DT_SCALE = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
Q_BLOCK = 256       # query rows attended at once
HEAD_ROWS = 128     # LM-head rows are padded to a multiple of this


def dims(cfg: dict) -> dict:
    H, NH = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"H": H, "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "NH": NH, "NKV": cfg["num_key_value_heads"],
            "D": cfg.get("head_dim") or H // NH,
            "I": cfg["mamba_expand"] * H, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
            "eps": float(cfg["rms_norm_eps"]), "dtype": cfg["torch_dtype"]}


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _key(root, name, layer=None):
    key = jax.random.fold_in(root, LEAVES.index(name))
    return key if layer is None else jax.random.fold_in(key, layer)


def _sym_quant(w, axis, levels):
    a = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(a / levels, 1e-12)
    return jnp.round(w / s) * s


def _matrix(key, shape, fan_in, precision, dtype, scale=1.0):
    """The weight definition, as float32 values: a Gaussian rounded to the
    dtype the configuration serves its weights in."""
    w = (jax.random.normal(key, shape, jnp.float32)
         * (fan_in ** -0.5 * scale)).astype(dtype).astype(jnp.float32)
    return _sym_quant(w, 0, 7.0) if precision == "w4" else w


def _gain(key, shape, dtype):
    return (1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype).astype(jnp.float32)


def _conv_bias(key, shape, dtype):
    return (CONV_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype).astype(jnp.float32)


def _dt_bias(key, shape):
    """softplus^-1 of a step size drawn log-uniform in [DT_MIN, DT_MAX]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))


def _act(x, precision):
    """What enters a matrix product: float32, or per-row int8 for "a8"."""
    return _sym_quant(x, -1, 127.0) if precision == "a8" else x


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _attention(q, k, v):
    """Causal GQA over one sequence. q [T, NH, D], k/v [T, NKV, D]."""
    T, NH, D = q.shape
    NKV = k.shape[1]
    qb_rows = min(Q_BLOCK, T)
    qg = q.reshape(T // qb_rows, qb_rows, NKV, NH // NKV, D)
    kv_pos = jnp.arange(T)

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) * D ** -0.5
        see = (start + jnp.arange(qb_rows))[:, None] >= kv_pos[None, :]
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (qg, jnp.arange(T // qb_rows) * qb_rows))
    return out.reshape(T, NH * D)


def _mlp(root, layer, c, precision, dtype):
    """One layer's feed-forward (with its residual) over one sequence."""
    H, F = c["H"], c["F"]
    wg = _matrix(_key(root, "w_gate", layer), (H, F), H, precision, dtype)
    wu = _matrix(_key(root, "w_up", layer), (H, F), H, precision, dtype)
    wd = _matrix(_key(root, "w_down", layer), (F, H), F, precision, dtype)
    n2 = _gain(_key(root, "norm2", layer), (H,), dtype)

    def one(xs):
        h = _act(_rms_norm(xs, n2, c["eps"]), precision)
        return xs + _act(jax.nn.silu(h @ wg) * (h @ wu), precision) @ wd

    return one


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _embed(root, tokens, cfg_t, precision):
    c = dict(cfg_t)
    w = _matrix(_key(root, "embed"), (c["V"], c["H"]), c["H"], precision,
                getattr(jnp, c["dtype"]))
    return jnp.take(w, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _attention_layer(root, layer, x, cfg_t, precision):
    """One attention block over x [B, T, H]; ``layer`` (traced) keys its
    weights."""
    c = dict(cfg_t)
    H, NH, NKV, D, eps = c["H"], c["NH"], c["NKV"], c["D"], c["eps"]
    dtype = getattr(jnp, c["dtype"])

    def mat(name, shape, fan_in):
        return _matrix(_key(root, name, layer), shape, fan_in, precision,
                       dtype)

    with jax.default_matmul_precision("highest"):
        wq, wk = mat("wq", (H, NH * D), H), mat("wk", (H, NKV * D), H)
        wv, wo = mat("wv", (H, NKV * D), H), mat("wo", (NH * D, H), NH * D)
        n1 = _gain(_key(root, "norm1", layer), (H,), dtype)
        mlp = _mlp(root, layer, c, precision, dtype)
        T = x.shape[1]

        def one(xs):
            h = _act(_rms_norm(xs, n1, eps), precision)
            q = (h @ wq).reshape(T, NH, D)
            k = (h @ wk).reshape(T, NKV, D)
            v = (h @ wv).reshape(T, NKV, D)
            if precision == "a8":
                k, v = _act(k, precision), _act(v, precision)
            a = _attention(q, k, v)
            return mlp(xs + _act(a, precision) @ wo)

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _mamba_layer(root, layer, x, cfg_t, precision):
    """One Mamba block over x [B, T, H]; ``layer`` (traced) keys its
    weights. The recurrence is a scan over single tokens."""
    c = dict(cfg_t)
    H, I, N, K, R, eps = c["H"], c["I"], c["N"], c["K"], c["R"], c["eps"]
    dtype = getattr(jnp, c["dtype"])

    def mat(name, shape, fan_in, scale=1.0):
        return _matrix(_key(root, name, layer), shape, fan_in, precision,
                       dtype, scale)

    def gain(name, shape):
        return _gain(_key(root, name, layer), shape, dtype)

    with jax.default_matmul_precision("highest"):
        w_in, w_x = mat("w_in", (H, 2 * I), H), mat("w_x", (I, R + 2 * N), I)
        w_dt, w_out = mat("w_dt", (R, I), R, DT_SCALE), mat("w_out", (I, H), I)
        w_c = mat("conv_w", (I, K), K)
        b_c = _conv_bias(_key(root, "conv_b", layer), (I,), dtype)
        b_dt = _dt_bias(_key(root, "b_dt", layer), (I,))
        n1, n_dt = gain("norm1", (H,)), gain("dt_norm", (R,))
        n_b, n_c = gain("b_norm", (N,)), gain("c_norm", (N,))
        # S4D-real, not drawn: the same in every channel and layer
        A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (I, N))
        D_skip = jnp.ones((I,), jnp.float32)
        mlp = _mlp(root, layer, c, precision, dtype)
        T = x.shape[1]

        def step(state, at):
            c_t, d_t, b_t, cm_t = at
            state = (jnp.exp(d_t[:, None] * A) * state
                     + (d_t * c_t)[:, None] * b_t[None, :])
            if precision == "a8":       # the state's precision below float32
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, state @ cm_t + D_skip * c_t

        def one(xs):
            h = _act(_rms_norm(xs, n1, eps), precision)
            u, z = jnp.split(h @ w_in, 2, axis=-1)
            padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
            conv = jax.nn.silu(b_c + sum(
                w_c[:, j] * padded[j:j + T] for j in range(K)))
            r, b, cm = jnp.split(_act(conv, precision) @ w_x, (R, R + N),
                                 axis=-1)
            r, b, cm = (_rms_norm(r, n_dt, eps), _rms_norm(b, n_b, eps),
                        _rms_norm(cm, n_c, eps))
            d = jax.nn.softplus(_act(r, precision) @ w_dt + b_dt)
            _, y = jax.lax.scan(step, jnp.zeros((I, N), jnp.float32),
                                (conv, d, b, cm))
            return mlp(xs + _act(y * jax.nn.silu(z), precision) @ w_out)

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _head(root, x, cfg_t, precision):
    c = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        dtype = getattr(jnp, c["dtype"])
        w = _matrix(_key(root, "embed"), (c["V"], c["H"]), c["H"], precision,
                    dtype)
        g = _gain(_key(root, "final_norm"), (c["H"],), dtype)
        return _act(_rms_norm(x, g, c["eps"]), precision) @ w.T


def logits_at(cfg: dict, seed: int, sequences: list[np.ndarray],
              positions: list[np.ndarray], pad_to: int,
              precision: str = "f32") -> list[np.ndarray]:
    """Full forward of each token sequence; returns, per sequence, the float32
    logits [len(positions[i]), V] at the given positions (row p holds the
    distribution of token p + 1). Sequences are padded to ``pad_to`` rows (a
    multiple of Q_BLOCK, or one block) so that every run of a cell compiles
    the same programs; every layer is causal, which keeps the padding out of
    real rows."""
    assert max(len(s) for s in sequences) <= pad_to
    assert pad_to % Q_BLOCK == 0 or pad_to < Q_BLOCK
    assert cfg["num_experts"] == 1 and cfg["tie_word_embeddings"]
    cfg_t = tuple(sorted(dims(cfg).items()))
    root = jax.random.key(int(seed))
    tokens = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    x = _embed(root, jnp.asarray(tokens), cfg_t, precision)
    for layer in range(cfg["num_hidden_layers"]):
        block = _attention_layer if is_attention(cfg, layer) else _mamba_layer
        x = block(root, jnp.int32(layer), x, cfg_t, precision)
    out = []
    for i, pos in enumerate(positions):
        padded = -(-len(pos) // HEAD_ROWS) * HEAD_ROWS   # few head shapes
        idx = np.zeros(padded, np.int32)
        idx[:len(pos)] = pos
        out.append(np.asarray(_head(root, x[i][idx], cfg_t, precision)
                              [:len(pos)]))
    return out
