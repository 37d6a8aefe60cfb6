"""Plain float32 reference of a decoder of Mamba-2 mixers with an attention
layer among them and an expert layer in every layer (the ``granitemoehybrid``
block of ibm-granite/granite-4.0-h-small). ``x`` is ``[T, H]``, every norm an
RMSNorm with a gain; r = residual_multiplier.

    x0 = embedding_multiplier * E[tokens]                       no positions
    x = x + r Mix_l(N1_l x);  x = x + r (MoE_l(N2_l x) + Shared_l(N2_l x))
    logits = (N_f(x) E^T) / logits_scaling                      tied head
    Shared(h) = (silu(h Sg) * (h Su)) Sd                        every token
    MoE(h):   l = h Wr [E];  sel = top_k(l);  w = softmax(l[sel])
              sum over the experts e HELD here of w_e (silu(h Ge) * (h Ue)) De
              (what the absent experts would add is left out: the share)
    Attn(h) (layer_types[i] == "attention"):
              q = h Wq [NH x D], k = h Wk, v = h Wv [NKV x D],
              causal softmax(attention_multiplier * q k^T) v, then Wo
    Mamba(h): [z | u | dt] = h W_in          I | I + 2 N | heads, no bias
              xBC_t = silu(b_c + sum_j w_c[:, j] u_{t-(K-1)+j})   u_t = 0, t < 0
              x, B, C = split(xBC);  d_t = softplus(dt_t + b_dt);  A = -exp(A_log)
              S_t[h] = exp(d_t[h] A[h]) S_{t-1}[h] + d_t[h] x_t[h] (x) B_t   S_-1 = 0
              y_t[h] = S_t[h] C_t + D[h] x_t[h]
              out = N_I(y * silu(z)) W_out        the gate BEFORE the norm,
                                                  the norm over all I channels

No cache, no state handed on, no chunks (the recurrence is a ``lax.scan`` over
single tokens: this is what the program's chunked matrix form is checked
against), no kernel, no batching across requests, no bf16 arithmetic: every
product runs under ``jax.default_matmul_precision("highest")``, one sequence
at a time, attention in query blocks, a held expert at a time over all the
tokens.

It imports nothing of the program. The benchmark DEFINES the served weights:
a drawn leaf is seeded under the key folded from (seed, the leaf's index in
``LEAVES``, the layer's number in the model, the expert's number among all the
router scores). A matrix is a Gaussian of standard deviation ``fan_in ** -0.5``
rounded to bfloat16 as the configuration states (``W_in``'s step-size columns
a tenth of that and its ``B`` and ``C`` columns twice that, so that the state
and not the skip ``D x`` is most of what the mixer's norm sees; ``Wq`` and
``Wk`` ``head_dim ** 0.25`` times that, so that the scores under the published
multiplier have deviation 1; the embedding ``1 / embedding_multiplier`` times
that, so that ``x0`` is a row of deviation ``H ** -0.5`` and a token's own
logit, the head being tied, is one deviation above the rest and not twelve);
norm gains ``1 + 0.1 g``, the final norm's times ``logits_scaling *
embedding_multiplier`` (logits of deviation 1.0 after the division); the
router float32; the convolution's bias ``0.1 g``. The scan's own leaves leave the state a LONG memory (a head's decay a
step runs from 0.9999 to 0.2), so that a lost or stale state shows in the
logits: ``A`` uniform in [1, 16] a head, ``D = 1``, ``b_dt`` the inverse
softplus of a step size log-uniform in [1e-3, 1e-1] (all float32). The
program's checkpoint-less boot follows the same recipe (``tests/bench`` pins
the two at a tiny size).

``precision`` selects the lower-precision controls the limits have to reject
(never used by a benchmark run): "a8" int8 activations into every matrix
product, an int8 KV AND the scan state rounded to bfloat16 after every step,
the step below what the configuration states (bf16 activations, a float32
state); "w4" int4 weights. The router's own product stays float32 in both.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("embed", "final_norm", "norm1", "norm2", "router", "s_gate", "s_up",
          "s_down", "e_gate", "e_up", "e_down", "wq", "wk", "wv", "wo",
          "w_in", "w_dt", "conv_w", "conv_b", "b_dt", "a_log", "mixer_norm",
          "w_out")
GAIN_STD = 0.1
CONV_BIAS_STD = 0.1
DT_SCALE = 0.1
BC_SCALE = 2.0
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0
Q_BLOCK = 256       # query rows attended at once
HEAD_ROWS = 128     # LM-head rows are padded to a multiple of this
NEAR_TIE = 1e-3


def dims(cfg: dict) -> dict:
    H, NH = cfg["hidden_size"], cfg["num_attention_heads"]
    first, count = cfg["experts_held"]
    MH, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    assert cfg["mamba_n_groups"] == 1     # B and C shared by every head
    return {"H": H, "Im": cfg["intermediate_size"],
            "Is": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
            "NH": NH, "NKV": cfg["num_key_value_heads"],
            "D": cfg.get("head_dim") or H // NH,
            "MH": MH, "P": P, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "E": cfg["router_experts"],
            "top": cfg["num_experts_per_tok"], "first": first, "count": count,
            "emb": float(cfg["embedding_multiplier"]),
            "res": float(cfg["residual_multiplier"]),
            "att": float(cfg["attention_multiplier"]),
            "div": float(cfg["logits_scaling"]),
            "eps": float(cfg["rms_norm_eps"]), "dtype": cfg["torch_dtype"]}


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


def _matrix(key, shape, fan_in, precision, dtype, scale=1.0):
    """The weight definition, as float32 values: a Gaussian rounded to the
    dtype the configuration serves its weights in."""
    w = (jax.random.normal(key, shape, jnp.float32)
         * (fan_in ** -0.5 * scale)).astype(dtype).astype(jnp.float32)
    return _sym_quant(w, 0, 7.0) if precision == "w4" else w


def _gain(key, shape, dtype, times=1.0):
    return ((1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32))
            * times).astype(dtype).astype(jnp.float32)


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


def _swiglu(h, wg, wu, wd, precision):
    return _act(jax.nn.silu(h @ wg) * (h @ wu), precision) @ wd


def _attention(q, k, v, scale):
    """Causal GQA over one sequence. q [T, NH, D], k/v [T, NKV, D]; the
    scores are multiplied by ``scale``."""
    T, NH, D = q.shape
    NKV = k.shape[1]
    qb_rows = min(Q_BLOCK, T)
    qg = q.reshape(T // qb_rows, qb_rows, NKV, NH // NKV, D)
    kv_pos = jnp.arange(T)

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) * scale
        see = (start + jnp.arange(qb_rows))[:, None] >= kv_pos[None, :]
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (qg, jnp.arange(T // qb_rows) * qb_rows))
    return out.reshape(T, NH * D)


def _moe(root, layer, c, precision, dtype):
    """One layer's second half (with its residual) over one sequence:
    returns (x', near-tie flags [T])."""
    H, Im, Is, E, top = c["H"], c["Im"], c["Is"], c["E"], c["top"]

    def mat(name, shape, fan_in, expert=None):
        return _matrix(_key(root, name, layer, expert), shape, fan_in,
                       precision, dtype)

    n2 = _gain(_key(root, "norm2", layer), (H,), dtype)
    # float32 as served, in every precision: a control lowers the arithmetic
    # around the selection, not the selection's own weights
    wr = _matrix(_key(root, "router", layer), (H, E), H, "f32", jnp.float32)
    s1, s3 = mat("s_gate", (H, Is), H), mat("s_up", (H, Is), H)
    s2 = mat("s_down", (Is, H), Is)

    def one(xs):
        h32 = _rms_norm(xs, n2, c["eps"])
        ranked, sel = jax.lax.top_k(h32 @ wr, top + 1)
        tie = ranked[:, top - 1] - ranked[:, top] < NEAR_TIE
        sel = sel[:, :top]
        w = jax.nn.softmax(ranked[:, :top], axis=-1)
        h = _act(h32, precision)
        m = _swiglu(h, s1, s3, s2, precision)

        def held(m, e):
            """Adds expert e's part for the tokens that chose it."""
            we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
            y = _swiglu(h, mat("e_gate", (H, Im), H, e),
                        mat("e_up", (H, Im), H, e),
                        mat("e_down", (Im, H), Im, e), precision)
            return m + y * we[:, None], None

        m, _ = jax.lax.scan(held, m, c["first"] + jnp.arange(c["count"]))
        return xs + c["res"] * m, tie

    return one


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _embed(root, tokens, cfg_t, precision):
    c = dict(cfg_t)
    w = _matrix(_key(root, "embed"), (c["V"], c["H"]), c["H"], precision,
                getattr(jnp, c["dtype"]), 1.0 / c["emb"])
    return jnp.take(w, tokens, axis=0) * c["emb"]


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _attention_layer(root, layer, x, cfg_t, precision):
    """One attention block over x [B, T, H]; ``layer`` (traced) keys its
    weights. Returns (x', near-tie flags [B, T])."""
    c = dict(cfg_t)
    H, NH, NKV, D, eps = c["H"], c["NH"], c["NKV"], c["D"], c["eps"]
    dtype = getattr(jnp, c["dtype"])

    def mat(name, shape, fan_in, scale=1.0):
        return _matrix(_key(root, name, layer), shape, fan_in, precision,
                       dtype, scale)

    with jax.default_matmul_precision("highest"):
        wide = D ** 0.25
        wq, wk = (mat("wq", (H, NH * D), H, wide),
                  mat("wk", (H, NKV * D), H, wide))
        wv, wo = mat("wv", (H, NKV * D), H), mat("wo", (NH * D, H), NH * D)
        n1 = _gain(_key(root, "norm1", layer), (H,), dtype)
        moe = _moe(root, layer, c, precision, dtype)
        T = x.shape[1]

        def one(xs):
            h = _act(_rms_norm(xs, n1, eps), precision)
            q = (h @ wq).reshape(T, NH, D)
            k = (h @ wk).reshape(T, NKV, D)
            v = (h @ wv).reshape(T, NKV, D)
            if precision == "a8":
                k, v = _act(k, precision), _act(v, precision)
            a = _attention(q, k, v, c["att"])
            return moe(xs + c["res"] * (_act(a, precision) @ wo))

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _mamba_layer(root, layer, x, cfg_t, precision):
    """One Mamba-2 block over x [B, T, H]; ``layer`` (traced) keys its
    weights. The recurrence is a scan over single tokens. Returns (x',
    near-tie flags [B, T])."""
    c = dict(cfg_t)
    H, MH, P, N, K, eps = c["H"], c["MH"], c["P"], c["N"], c["K"], c["eps"]
    I = MH * P
    C = I + 2 * N
    dtype = getattr(jnp, c["dtype"])

    def mat(name, shape, fan_in, scale=1.0):
        return _matrix(_key(root, name, layer), shape, fan_in, precision,
                       dtype, scale)

    with jax.default_matmul_precision("highest"):
        w_in = jnp.concatenate([
            mat("w_in", (H, I + C), H) * jnp.where(
                jnp.arange(I + C) >= 2 * I, BC_SCALE, 1.0),
            mat("w_dt", (H, MH), H, DT_SCALE)], axis=1)
        w_out = mat("w_out", (I, H), I)
        w_c = mat("conv_w", (C, K), K)
        b_c = (CONV_BIAS_STD * jax.random.normal(
            _key(root, "conv_b", layer), (C,), jnp.float32)
               ).astype(dtype).astype(jnp.float32)
        b_dt = _dt_bias(_key(root, "b_dt", layer), (MH,))
        A = -jax.random.uniform(_key(root, "a_log", layer), (MH,),
                                jnp.float32, A_MIN, A_MAX)
        D_skip = jnp.ones((MH,), jnp.float32)
        n1 = _gain(_key(root, "norm1", layer), (H,), dtype)
        n_y = _gain(_key(root, "mixer_norm", layer), (I,), dtype)
        moe = _moe(root, layer, c, precision, dtype)
        T = x.shape[1]

        def step(state, at):
            x_t, d_t, b_t, c_t = at         # [MH, P], [MH], [N], [N]
            state = (jnp.exp(d_t * A)[:, None, None] * state
                     + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
            if precision == "a8":       # the state's precision below float32
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, state @ c_t + D_skip[:, None] * x_t

        def one(xs):
            h = _act(_rms_norm(xs, n1, eps), precision)
            z, u, dt = jnp.split(h @ w_in, (I, I + C), axis=-1)
            padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
            conv = jax.nn.silu(b_c + sum(
                w_c[:, j] * padded[j:j + T] for j in range(K)))
            xc, b, cm = jnp.split(conv, (I, I + N), axis=-1)
            d = jax.nn.softplus(dt + b_dt)
            _, y = jax.lax.scan(step, jnp.zeros((MH, P, N), jnp.float32),
                                (xc.reshape(T, MH, P), d, b, cm))
            g = _rms_norm(y.reshape(T, I) * jax.nn.silu(z), n_y, eps)
            return moe(xs + c["res"] * (_act(g, precision) @ w_out))

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _head(root, x, cfg_t, precision):
    c = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        dtype = getattr(jnp, c["dtype"])
        w = _matrix(_key(root, "embed"), (c["V"], c["H"]), c["H"], precision,
                    dtype, 1.0 / c["emb"])
        g = _gain(_key(root, "final_norm"), (c["H"],), dtype,
                  c["div"] * c["emb"])
        return _act(_rms_norm(x, g, c["eps"]), precision) @ w.T / c["div"]


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
    assert cfg["tie_word_embeddings"] and not cfg["mamba_proj_bias"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    cfg_t = tuple(sorted(dims(cfg).items()))
    root = jax.random.key(int(seed))
    tokens = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    x = _embed(root, jnp.asarray(tokens), cfg_t, precision)
    ties = jnp.zeros(tokens.shape, bool)
    for layer, kind in enumerate(cfg["layer_types"]):
        block = _attention_layer if kind == "attention" else _mamba_layer
        x, tie = block(root, jnp.int32(layer), x, cfg_t, precision)
        ties = ties | tie
    ties = np.asarray(ties)
    out = []
    for i, pos in enumerate(positions):
        padded = -(-len(pos) // HEAD_ROWS) * HEAD_ROWS   # few head shapes
        idx = np.zeros(padded, np.int32)
        idx[:len(pos)] = pos
        out.append(np.asarray(_head(root, x[i][idx], cfg_t, precision)
                              [:len(pos)]))
    if precision == "f32" and out:
        tied = np.concatenate([ties[i][np.asarray(p)]
                               for i, p in enumerate(positions)])
        top = dims(cfg)["top"]
        print(f"reference: {int(tied.sum())} of {tied.size} sampled positions "
              f"have a router near-tie (choices {top} and {top + 1} within "
              f"{NEAR_TIE} in some layer)", flush=True)
    return out
