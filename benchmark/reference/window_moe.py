"""Plain float32 reference of a window-and-full attention decoder with an
expert layer (the ``afmoe`` block of arcee-ai/Trinity-Large-Preview), cut to
one chip's share of an expert-parallel deployment.

    x0 = E[tokens] * sqrt(hidden)
    a = Attn_l(N1 x);  x = x + N2(a);  m = MLP_l(N3 x);  x = x + N4(m)
    logits = N_f(x) W_head

``Attn``: q, k, v and a gate G projected from h; q and k RMS-normed per head;
rotary (split-half, full head) on ``sliding_attention`` layers only, which
also see just the last ``sliding_window`` positions; ``full_attention`` layers
use no positional encoding; softmax(q k^T / sqrt(d)) v * sigmoid(G), then Wo.
``MLP``: a SwiGLU on the leading dense layers, else
``Shared(h) + sum_{e in top4(sigmoid(h Wr) + b), e held} w_e Expert_e(h)`` with
``w = s[sel] / (sum s[sel] + 1e-20) * route_scale``: the router scores all
``router_experts``, this chip adds the part of the experts it holds
(``experts_held``); the part of absent experts is left out, as on the chip.

No cache, no ring, no batching across requests, no bf16 arithmetic: every
product runs under ``jax.default_matmul_precision("highest")``, one sequence at
a time, attention in query blocks so that 8192 rows fit.

It imports nothing of the program. The benchmark DEFINES the served weights:
a leaf is a seeded Gaussian under the key folded from (seed, the leaf's index
in ``LEAVES``, layer, expert), of standard deviation ``fan_in ** -0.5``,
rounded to bfloat16 as the configuration states (norm gains 1 + 0.1 g; the
selection bias 0.02 g, a tenth of the scores' spread, so that it changes
selections; router and bias stay float32). The program's checkpoint-less boot
follows the same recipe (``tests/bench`` pins the two at a tiny size).

``precision`` selects the lower-precision controls the limits have to reject
(never used by a benchmark run): "a8" int8 activations into every matrix
product and an int8 KV, the step below bf16 activations; "w4" int4 weights.

A selection is a discontinuity: where a token's 4th and 5th choices lie within
1e-3, bf16 activations can pick the other expert. Every call prints how many
of the sampled positions are such near-ties and the widest gap among them and
among the rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("embed", "lm_head", "final_norm", "norm1", "norm2", "norm3",
          "norm4", "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo",
          "w_gate", "w_up", "w_down", "router", "bias", "s_gate", "s_up",
          "s_down", "e_gate", "e_up", "e_down")
BIAS_STD = 0.02
GAIN_STD = 0.1
SLIDING = "sliding_attention"
Q_BLOCK = 256       # query rows attended at once
HEAD_ROWS = 128     # LM-head rows are padded to a multiple of this
NEAR_TIE = 1e-3


def dims(cfg: dict) -> dict:
    first, count = cfg["experts_held"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "NH": cfg["num_attention_heads"],
            "NKV": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "E": cfg["router_experts"], "K": cfg["num_experts_per_tok"],
            "first": first, "count": count, "W": cfg["sliding_window"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "route_scale": float(cfg["route_scale"]),
            "route_norm": bool(cfg["route_norm"]),
            "dtype": cfg["torch_dtype"]}


def _key(root, name, layer=None, expert=None):
    key = jax.random.fold_in(root, LEAVES.index(name))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    return key


def _sym_quant(w, axis, levels):
    a = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(a / levels, 1e-12)
    return jnp.round(w / s) * s


def _matrix(key, shape, fan_in, precision, dtype):
    """The weight definition, as float32 values: a Gaussian rounded to the
    dtype the configuration serves its weights in."""
    w = (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
         ).astype(dtype).astype(jnp.float32)
    return _sym_quant(w, 0, 7.0) if precision == "w4" else w


def _gain(key, shape, dtype):
    return (1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype).astype(jnp.float32)


def _act(x, precision):
    """What enters a matrix product: float32, or per-row int8 for "a8"."""
    return _sym_quant(x, -1, 127.0) if precision == "a8" else x


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window):
    """Causal GQA over one sequence, on a window layer 0 <= i - j < window.
    q [T, NH, D], k/v [T, NKV, D]."""
    T, NH, D = q.shape
    NKV = k.shape[1]
    qb_rows = min(Q_BLOCK, T)
    qg = q.reshape(T // qb_rows, qb_rows, NKV, NH // NKV, D)
    kv_pos = jnp.arange(T)

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) * D ** -0.5
        back = (start + jnp.arange(qb_rows))[:, None] - kv_pos[None, :]
        see = back >= 0
        if window is not None:
            see &= back < window
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (qg, jnp.arange(T // qb_rows) * qb_rows))
    return out.reshape(T, NH * D)


def _swiglu(h, wg, wu, wd, precision):
    return _act(jax.nn.silu(h @ wg) * (h @ wu), precision) @ wd


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _embed(root, tokens, cfg_t, precision):
    c = dict(cfg_t)
    w = _matrix(_key(root, "embed"), (c["V"], c["H"]), c["H"], precision,
                getattr(jnp, c["dtype"]))
    return jnp.take(w, tokens, axis=0) * c["H"] ** 0.5


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision", "dense",
                                             "sliding"))
def _layer(root, layer, x, cfg_t, precision, dense, sliding):
    """One block over x [B, T, H]; ``layer`` (traced) keys its weights.
    Returns (x', near-tie flags [B, T])."""
    c = dict(cfg_t)
    H, NH, NKV, D, eps = c["H"], c["NH"], c["NKV"], c["D"], c["eps"]
    dtype = getattr(jnp, c["dtype"])

    def mat(name, shape, fan_in, expert=None):
        return _matrix(_key(root, name, layer, expert), shape, fan_in,
                       precision, dtype)

    def gain(name, shape):
        return _gain(_key(root, name, layer), shape, dtype)

    with jax.default_matmul_precision("highest"):
        wq, wk = mat("wq", (H, NH * D), H), mat("wk", (H, NKV * D), H)
        wv, wg = mat("wv", (H, NKV * D), H), mat("wg", (H, NH * D), H)
        wo = mat("wo", (NH * D, H), NH * D)
        n1, n2, n3, n4 = (gain(f"norm{i}", (H,)) for i in (1, 2, 3, 4))
        qn, kn = gain("q_norm", (D,)), gain("k_norm", (D,))
        T = x.shape[1]
        pos = jnp.arange(T)

        def attn(xs):
            h = _act(_rms_norm(xs, n1, eps), precision)
            q = _rms_norm((h @ wq).reshape(T, NH, D), qn, eps)
            k = _rms_norm((h @ wk).reshape(T, NKV, D), kn, eps)
            v = (h @ wv).reshape(T, NKV, D)
            if sliding:
                q, k = _rope(q, pos, c["theta"]), _rope(k, pos, c["theta"])
            if precision == "a8":
                k, v = _act(k, precision), _act(v, precision)
            a = _attention(q, k, v, c["W"] if sliding else None)
            a = a * jax.nn.sigmoid(h @ wg)
            return xs + _rms_norm(_act(a, precision) @ wo, n2, eps)

        if dense:
            I = c["I"]
            w1, w3 = mat("w_gate", (H, I), H), mat("w_up", (H, I), H)
            w2 = mat("w_down", (I, H), I)

            def one(xs):
                xs = attn(xs)
                h = _act(_rms_norm(xs, n3, eps), precision)
                m = _swiglu(h, w1, w3, w2, precision)
                return xs + _rms_norm(m, n4, eps), jnp.zeros((T,), bool)
        else:
            Im, E, K = c["Im"], c["E"], c["K"]
            # float32 as served, in every precision: a control lowers the
            # arithmetic around the selection, not the selection's own weights
            wr = _matrix(_key(root, "router", layer), (H, E), H, "f32",
                         jnp.float32)
            bias = BIAS_STD * jax.random.normal(
                _key(root, "bias", layer), (E,), jnp.float32)
            s1, s3 = mat("s_gate", (H, Im), H), mat("s_up", (H, Im), H)
            s2 = mat("s_down", (Im, H), Im)

            def one(xs):
                xs = attn(xs)
                h32 = _rms_norm(xs, n3, eps)
                s = jax.nn.sigmoid(h32 @ wr)
                ranked, sel = jax.lax.top_k(s + bias, K + 1)
                tie = ranked[:, K - 1] - ranked[:, K] < NEAR_TIE
                sel = sel[:, :K]
                w = jnp.take_along_axis(s, sel, axis=-1)
                if c["route_norm"]:
                    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
                w = w * c["route_scale"]
                h = _act(h32, precision)
                m = _swiglu(h, s1, s3, s2, precision)

                def held(m, e):
                    """Adds expert e's part for the tokens that chose it."""
                    we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
                    y = _swiglu(h, mat("e_gate", (H, Im), H, e),
                                mat("e_up", (H, Im), H, e),
                                mat("e_down", (Im, H), Im, e), precision)
                    return m + y * we[:, None], None

                m, _ = jax.lax.scan(held, m,
                                    c["first"] + jnp.arange(c["count"]))
                return xs + _rms_norm(m, n4, eps), tie

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _head(root, x, cfg_t, precision):
    c = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        dtype = getattr(jnp, c["dtype"])
        w = _matrix(_key(root, "lm_head"), (c["H"], c["V"]), c["H"], precision,
                    dtype)
        g = _gain(_key(root, "final_norm"), (c["H"],), dtype)
        return _act(_rms_norm(x, g, c["eps"]), precision) @ w


def logits_at(cfg: dict, seed: int, sequences: list[np.ndarray],
              positions: list[np.ndarray], pad_to: int,
              precision: str = "f32") -> list[np.ndarray]:
    """Full forward of each token sequence; returns, per sequence, the float32
    logits [len(positions[i]), V] at the given positions (row p holds the
    distribution of token p + 1). Sequences are padded to ``pad_to`` rows (a
    multiple of Q_BLOCK, or one block) so that every run of a cell compiles
    the same programs; causal attention keeps the padding out of real rows."""
    assert max(len(s) for s in sequences) <= pad_to
    assert pad_to % Q_BLOCK == 0 or pad_to < Q_BLOCK
    cfg_t = tuple(sorted(dims(cfg).items()))
    root = jax.random.key(int(seed))
    tokens = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    x = _embed(root, jnp.asarray(tokens), cfg_t, precision)
    ties = jnp.zeros(tokens.shape, jnp.int32)
    for layer, layer_type in enumerate(cfg["layer_types"]):
        x, tie = _layer(root, jnp.int32(layer), x, cfg_t, precision,
                        layer < cfg["num_dense_layers"],
                        layer_type == SLIDING)
        ties = ties + tie
    ties = np.asarray(ties)
    out, tied, gaps = [], [], []
    for i, pos in enumerate(positions):
        padded = -(-len(pos) // HEAD_ROWS) * HEAD_ROWS   # few head shapes
        idx = np.zeros(padded, np.int32)
        idx[:len(pos)] = pos
        logits = np.asarray(_head(root, x[i][idx], cfg_t, precision)
                            [:len(pos)])
        out.append(logits)
        pos = np.asarray(pos)
        nxt = np.minimum(pos + 1, len(sequences[i]) - 1)
        gaps.append(logits.max(-1) - logits[np.arange(len(pos)),
                                            np.asarray(sequences[i])[nxt]])
        tied.append(ties[i][pos] > 0)
    tied, gaps = np.concatenate(tied), np.concatenate(gaps)
    print(f"reference window_moe ({precision}): {int(tied.sum())} of "
          f"{tied.size} sampled positions have a router near-tie (4th and 5th "
          f"choice within {NEAR_TIE} in some expert layer); widest gap of the "
          f"sequence's next token among them "
          f"{float(gaps[tied].max()) if tied.any() else 0.0:.5f}, among the "
          f"rest {float(gaps[~tied].max()) if (~tied).any() else 0.0:.5f}",
          flush=True)
    return out
