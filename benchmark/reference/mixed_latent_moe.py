"""Plain float32 reference of a decoder whose layers are of TWO kinds, each
with a latent attention of its own, over an expert layer with sigmoid routing
(the ``dots3_note`` block of dots-studio/dots3-note-prev), cut to one chip's
share of an expert-parallel deployment. With ``N`` an RMSNorm (eps 1e-5),
``R`` / ``R'`` the rotation of a 64-wide part at the token's position (``R``
pairs neighbours, ``R'`` split halves; plain frequencies of the KIND's own
base, no scaling), ``h = N1(x)``, and a layer's kind giving ``(heads, q_rank,
kv_rank, nope, rope, v, theta)``: full ``(128, 1024, 512, 128, 64, 128, 8e7)``,
sliding ``(64, 1024, 1024, 192, 64, 128, 5e4)``:

    cq  = s_q Nq(h Wq_a);  q_i = cq Wq_b -> heads of [q_nope_i | R(q_rope_i)]
    ckv | k_r = h Wkv_a;  c = s_kv Nkv(ckv),  kr = R(k_r)
        s_q = sqrt(hidden / q_rank), s_kv = sqrt(hidden / kv_rank)
        (``apply_mla_qkv_lora_rescale``; 1 without it)
    k_nope_i | v_i = c Wkv_b
    full layers, the indexer:
        qI_j = cq WI_q -> 64 heads of 128, the first 64 under R'
        kI   = LayerNorm(h WI_k), the first 64 under R'
        w_j  = (h WI_w)_j * 64^-0.5 * 128^-0.5
        I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
        S_t  = the min(index_topk, t + 1) positions s <= t of largest I(t, s)
    sliding layers:  S_t = the positions s with 0 <= t - s < sliding_window
    a_i(t) = softmax over S_t of ((q_nope_i . k_nope_i(s) + R(q_rope_i) . kr(s))
                                  * (nope + rope)^-0.5)
    g = sigmoid(h Wg)            (a value a head: ``headwise``)
    x += concat_i(g_i sum_s a_i(t, s) v_i(s)) Wo
    layer 0:        x += SwiGLU(N2 x)
    expert layers:  s = sigmoid(float32(N2 x) Wr);  sel = top 8 of s + b
        g = s[sel] / sum(s[sel]) * routed_scaling_factor
        x += Shared(N2 x) + sum_{e in sel, e held} g_e Expert_e(N2 x)
    logits = N_f(x) W_head

The router scores all ``router_experts``; this chip adds the part of the
experts it holds (``experts_held``); the part of absent experts is left out, as
on the chip. No cache, no ring, no absorbed form, no batching, no bf16
arithmetic: the full forward in the expanded form, one sequence at a time,
every product under ``jax.default_matmul_precision("highest")``, attention in
query blocks and heads in groups so that 20480 rows fit (a sliding layer's
block takes the slice of keys that holds its window, a full layer's every
key).

It imports nothing of the program. The benchmark DEFINES the served weights: a
leaf is a seeded Gaussian under the key folded from (seed, the leaf's index in
``LEAVES``, layer, expert), of standard deviation ``fan_in ** -0.5``, rounded
to bfloat16 as the configuration states (the embedding's rows at unit
variance; norm gains 1 + 0.1 g, the LayerNorm's shift 0.1 g; router and bias
float32). ONE departure from ``fan_in ** -0.5``: under
``apply_mla_qkv_lora_rescale`` the matrices that read a rescaled latent
(``wq_b``, ``wkv_b``, the indexer's ``wi_q``) are drawn at ``hidden ** -0.5``,
the width the rescale is there for: ``sqrt(hidden / rank)`` x a normed latent
x such a matrix gives queries and keys of unit values. Drawn at the rank's
fan-in they give attention logits of deviation 7 (full) and 5 (sliding), a
softmax that is one position, and rounding decides which (on the chip six sound
bf16 runs then read ``gap_mean`` 0.55-0.72 with 69% of the served tokens off
this reference's best). The selection bias gives every seed the same load: it
is fitted to the layer's router (``selection_bias``). The program's
checkpoint-less boot follows the same recipe (``tests/bench`` pins the two).

``precision`` selects the lower-precision controls the limits have to reject
(never used by a benchmark run): "a8" int8 activations into every matrix
product and int8 cached rows (both kinds' latent rows and the index key), the
step below bf16; "w4" int4 weights.

Two selections are discontinuities. Where a token's 8th and 9th expert lie
within 1e-3, or its 2048th and 2049th position within 1e-3 of the scores'
spread, bf16 arithmetic can pick the other one. Every call prints how many of
the sampled positions are such near-ties and the widest gap among them and
among the rest. A window is no such selection: which rows it holds is
arithmetic on positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("embed", "lm_head", "final_norm", "norm1", "norm2", "wq_a",
          "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wi_q", "wi_k",
          "wi_k_gain", "wi_k_shift", "wi_w", "w_gate", "w_up", "w_down",
          "router", "bias", "s_gate", "s_up", "s_down", "e_gate", "e_up",
          "e_down", "wg")
SLIDING, FULL = "sliding_attention", "full_attention"
GAIN_STD = 0.1
SHIFT_STD = 0.1
BIAS_SAMPLES = 1 << 16
BIAS_STEPS = 32
BIAS_STEP = 0.02
LN_EPS = 1e-6
Q_BLOCK = 256       # query rows attended at once
I_BLOCK = 64        # query rows whose index scores are taken at once
HEAD_GROUP = 16     # heads expanded at once
HEAD_ROWS = 128     # LM-head rows are padded to a multiple of this
NEAR_TIE = 1e-3
PADS = (1024, 2048, 4096, 8192, 16384, 32768)


def attention_of(cfg: dict, kind: str) -> dict:
    """The latent attention of one kind of layer, from the config's keys (a
    sliding layer's carry ``swa_`` before them)."""
    pre = "swa_" if kind == SLIDING else ""
    gate = cfg.get(("swa_" if kind == SLIDING else "") + "attention_gate_type")
    if gate not in (None, "headwise"):
        raise ValueError(f"attention gate {gate!r}")
    return {"NH": cfg[pre + "num_attention_heads"],
            "Q": cfg[pre + "q_lora_rank"], "R": cfg[pre + "kv_lora_rank"],
            "Dn": cfg[pre + "qk_nope_head_dim"],
            "Dr": cfg[pre + "qk_rope_head_dim"],
            "Dv": cfg[pre + "v_head_dim"],
            "theta": float(cfg[pre + "rope_theta"]),
            "window": cfg["sliding_window_size"] if kind == SLIDING else 0,
            "gate": gate == "headwise"}


def dims(cfg: dict) -> dict:
    if cfg.get("rope_scaling"):
        raise ValueError("this reference rotates under plain frequencies")
    first, count = cfg["experts_held"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            FULL: tuple(sorted(attention_of(cfg, FULL).items())),
            SLIDING: tuple(sorted(attention_of(cfg, SLIDING).items())),
            "Hi": cfg["index_n_heads"], "Di": cfg["index_head_dim"],
            "topk": cfg["index_topk"], "E": cfg["router_experts"],
            "K": cfg["num_experts_per_tok"], "first": first, "count": count,
            "eps": float(cfg["rms_norm_eps"]),
            "rescale": bool(cfg.get("apply_mla_qkv_lora_rescale")),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "route_norm": bool(cfg["norm_topk_prob"]),
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


def _gain(key, shape, dtype, std=GAIN_STD, mean=1.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype).astype(jnp.float32)


def _chosen(biased, k):
    """The k experts of largest biased score [T, E], and one more where
    there is one (the near-tie's other side)."""
    return jax.lax.top_k(biased, k + 1 if k < biased.shape[1] else k)


def selection_bias(key, router, gain, c: dict):
    """``e_score_correction_bias`` fitted to the layer's router, as the
    published training fits it, so that every seed offers every expert (and
    so every chip's block of them) the same load. A normed token of isotropic
    direction has logits N(0, A^T A), A = the norm's gain x the router: over
    BIAS_SAMPLES such draws the bias takes BIAS_STEPS steps against each
    expert's relative excess over the even load."""
    with jax.default_matmul_precision("highest"):
        a = gain[:, None] * router
        logits = jax.random.normal(key, (BIAS_SAMPLES, c["E"]), jnp.float32
                                   ) @ jnp.linalg.cholesky(a.T @ a).T
    s = jax.nn.sigmoid(logits)
    even = BIAS_SAMPLES * c["K"] / c["E"]

    def step(_, b):
        sel = _chosen(s + b, c["K"])[1][:, :c["K"]]
        load = jnp.zeros((c["E"],), jnp.float32).at[sel.reshape(-1)].add(1.0)
        return b - BIAS_STEP * (load / even - 1.0)

    return jax.lax.fori_loop(0, BIAS_STEPS, step,
                             jnp.zeros((c["E"],), jnp.float32))


def _act(x, precision):
    """What enters a matrix product: float32, or per-row int8 for "a8"."""
    return _sym_quant(x, -1, 127.0) if precision == "a8" else x


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def inv_freq(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def _rotate(x, positions, freqs, interleaved: bool):
    """x [T, heads, D] rotated whole; pairs are neighbours or split halves."""
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, wg, wu, wd, precision):
    return _act(jax.nn.silu(h @ wg) * (h @ wu), precision) @ wd


def _selection(qi, wts, ki, topk):
    """qI [T, Hi, Di], weights [T, Hi], kI [T, Di] -> (mask [T, T] bool: s in
    S_t; near [T] bool: the last position kept and the first left out lie
    within NEAR_TIE of the row's spread of scores)."""
    T = qi.shape[0]
    rows = min(I_BLOCK, T)
    k = min(topk, T)
    pos = jnp.arange(T)

    def block(a):
        qb, wb, start = a
        s = jnp.einsum("qhd,kd->qhk", qb, ki)
        score = jnp.einsum("qhk,qh->qk", jnp.maximum(s, 0.0), wb)
        see = pos[None, :] <= (start + jnp.arange(rows))[:, None]
        score = jnp.where(see, score, -jnp.inf)
        best = jax.lax.top_k(score, min(k + 1, T))[0]
        kth = best[:, k - 1:k]
        nxt = best[:, k] if k < T else jnp.full((rows,), -jnp.inf)
        spread = best[:, 0] - kth[:, 0]
        near = (kth[:, 0] - nxt) < NEAR_TIE * jnp.maximum(spread, 1e-9)
        return (score >= kth) & see, near & jnp.isfinite(nxt)

    mask, near = jax.lax.map(
        block, (qi.reshape(T // rows, rows, *qi.shape[1:]),
                wts.reshape(T // rows, rows, -1),
                jnp.arange(T // rows) * rows))
    return mask.reshape(T, T), near.reshape(T)


def _attention(q, k, v, mask, scale):
    """q, k [T, G, Dk], v [T, G, Dv], mask [T, T] -> [T, G, Dv]."""
    T = q.shape[0]
    rows = min(Q_BLOCK, T)

    def block(a):
        qb, mb = a
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(mb[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(T // rows, rows, *q.shape[1:]),
                              mask.reshape(T // rows, rows, T)))
    return out.reshape(T, *out.shape[2:])


def _window_attention(q, k, v, window, scale):
    """The same over the positions 0 <= t - s < window alone: a block of
    queries takes the slice of keys that can hold its windows (the block's
    own rows and the ``window - 1`` before its first, out of arrays with that
    many rows of nothing in front)."""
    T = q.shape[0]
    rows = min(Q_BLOCK, T)
    front = window - 1
    kp, vp = (jnp.pad(a, ((front, 0), (0, 0), (0, 0))) for a in (k, v))

    def block(a):
        qb, start = a
        kb = jax.lax.dynamic_slice_in_dim(kp, start, rows + front)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, rows + front)
        t = start + jnp.arange(rows)
        s_pos = start - front + jnp.arange(rows + front)
        back = t[:, None] - s_pos[None, :]
        see = (back >= 0) & (back < window) & (s_pos >= 0)[None, :]
        s = jnp.einsum("qhd,khd->hqk", qb, kb) * scale
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb)

    out = jax.lax.map(block, (q.reshape(T // rows, rows, *q.shape[1:]),
                              jnp.arange(T // rows) * rows))
    return out.reshape(T, *out.shape[2:])


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _embed(root, tokens, cfg_t, precision):
    c = dict(cfg_t)
    w = _matrix(_key(root, "embed"), (c["V"], c["H"]), 1, precision,
                getattr(jnp, c["dtype"]))
    return jnp.take(w, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _fitted_bias(root, layer, cfg_t):
    """The selection bias of expert layer ``layer``, once a call of
    ``logits_at``: fitted to the router and the norm the layer draws."""
    c = dict(cfg_t)
    dtype = getattr(jnp, c["dtype"])
    wr = _matrix(_key(root, "router", layer), (c["H"], c["E"]), c["H"], "f32",
                 jnp.float32)
    return selection_bias(_key(root, "bias", layer), wr,
                          _gain(_key(root, "norm2", layer), (c["H"],), dtype),
                          c)


@functools.partial(jax.jit,
                   static_argnames=("cfg_t", "precision", "dense", "kind"))
def _layer(root, layer, x, bias, cfg_t, precision, dense, kind):
    """One block over x [T, H]; ``layer`` (traced) keys its weights, ``bias``
    [E] is its selection bias, ``kind`` its attention's. Returns (x', router
    near-tie flags [T], selection near-tie flags [T])."""
    c = dict(cfg_t)
    a = dict(c[kind])
    H, NH, Q, R = c["H"], a["NH"], a["Q"], a["R"]
    Dn, Dr, Dv, Hi, Di = a["Dn"], a["Dr"], a["Dv"], c["Hi"], c["Di"]
    Dk, eps = Dn + Dr, c["eps"]
    dtype = getattr(jnp, c["dtype"])
    T = x.shape[0]
    pos = jnp.arange(T)
    freqs = inv_freq(Dr, a["theta"])
    s_q = (H / Q) ** 0.5 if c["rescale"] else 1.0
    s_kv = (H / R) ** 0.5 if c["rescale"] else 1.0
    # what reads a rescaled latent is drawn at the model's width (the module's
    # docstring says why)
    Fq, Fr = (H, H) if c["rescale"] else (Q, R)

    def mat(name, shape, fan_in, expert=None):
        return _matrix(_key(root, name, layer, expert), shape, fan_in,
                       precision, dtype)

    def gain(name, shape):
        return _gain(_key(root, name, layer), shape, dtype)

    with jax.default_matmul_precision("highest"):
        h = _act(_rms_norm(x, gain("norm1", (H,)), eps), precision)
        cq = _act(s_q * _rms_norm(h @ mat("wq_a", (H, Q), H),
                                  gain("q_norm", (Q,)), eps), precision)
        kv = h @ mat("wkv_a", (H, R + Dr), H)
        latent = s_kv * _rms_norm(kv[:, :R], gain("kv_norm", (R,)), eps)
        kr = _rotate(kv[:, None, R:], pos, freqs, True)[:, 0]
        if precision == "a8":       # an int8 cache holds these rows
            latent, kr = _act(latent, precision), _act(kr, precision)
        near = jnp.zeros((T,), bool)
        if not a["window"]:         # the indexer, under the full layers' base
            qi = (cq @ mat("wi_q", (Q, Hi * Di), Fq)).reshape(T, Hi, Di)
            qi = jnp.concatenate([_rotate(qi[..., :Dr], pos, freqs, False),
                                  qi[..., Dr:]], axis=-1)
            ki = h @ mat("wi_k", (H, Di), H)
            ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
            ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                                    + LN_EPS)
            ki = ki * gain("wi_k_gain", (Di,)) + _gain(
                _key(root, "wi_k_shift", layer), (Di,), dtype, SHIFT_STD, 0.0)
            ki = jnp.concatenate(
                [_rotate(ki[:, None, :Dr], pos, freqs, False)[:, 0],
                 ki[:, Dr:]], axis=-1)
            wts = (h @ mat("wi_w", (H, Hi), H)) * (Hi ** -0.5 * Di ** -0.5)
            if precision == "a8":
                ki, qi = _act(ki, precision), _act(qi, precision)
            mask, near = _selection(qi, wts, ki, c["topk"])

        # the expanded form, a group of heads at a time
        G = min(HEAD_GROUP, NH)
        wq_b = mat("wq_b", (Q, NH * Dk), Fq).reshape(Q, NH // G, G * Dk)
        wkv_b = mat("wkv_b", (R, NH * (Dn + Dv)), Fr).reshape(
            R, NH // G, G * (Dn + Dv))
        scale = Dk ** -0.5

        def group(g):
            q = (cq @ wq_b[:, g]).reshape(T, G, Dk)
            q = jnp.concatenate(
                [q[..., :Dn], _rotate(q[..., Dn:], pos, freqs, True)], -1)
            kvx = (_act(latent, precision) @ wkv_b[:, g]).reshape(
                T, G, Dn + Dv)
            k = jnp.concatenate(
                [kvx[..., :Dn], jnp.broadcast_to(kr[:, None], (T, G, Dr))],
                axis=-1)
            if a["window"]:
                return _window_attention(_act(q, precision), k, kvx[..., Dn:],
                                         a["window"], scale)
            return _attention(_act(q, precision), k, kvx[..., Dn:], mask,
                              scale)

        o = jax.lax.map(group, jnp.arange(NH // G))         # [NG, T, G, Dv]
        o = jnp.moveaxis(o, 0, 1).reshape(T, NH, Dv)
        if a["gate"]:
            o = o * jax.nn.sigmoid(h @ mat("wg", (H, NH), H))[:, :, None]
        x = x + _act(o.reshape(T, NH * Dv), precision) @ mat(
            "wo", (NH * Dv, H), NH * Dv)

        h32 = _rms_norm(x, gain("norm2", (H,)), eps)
        h = _act(h32, precision)
        if dense:
            I = c["I"]
            m = _swiglu(h, mat("w_gate", (H, I), H), mat("w_up", (H, I), H),
                        mat("w_down", (I, H), I), precision)
            return x + m, jnp.zeros((T,), bool), near
        Im, E, K = c["Im"], c["E"], c["K"]
        # float32 as served, in every precision: a control lowers the
        # arithmetic around the selection, not the selection's own weights
        wr = _matrix(_key(root, "router", layer), (H, E), H, "f32",
                     jnp.float32)
        s = jax.nn.sigmoid(h32 @ wr)
        ranked, sel = _chosen(s + bias, K)
        tie = ranked[:, K - 1] - ranked[:, K] < NEAR_TIE
        sel = sel[:, :K]
        w = jnp.take_along_axis(s, sel, axis=-1)
        if c["route_norm"]:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * c["route_scale"]
        m = _swiglu(h, mat("s_gate", (H, Im), H), mat("s_up", (H, Im), H),
                    mat("s_down", (Im, H), Im), precision)

        def held(m, e):
            """Adds expert e's part for the tokens that chose it."""
            we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
            y = _swiglu(h, mat("e_gate", (H, Im), H, e),
                        mat("e_up", (H, Im), H, e),
                        mat("e_down", (Im, H), Im, e), precision)
            return m + y * we[:, None], None

        m, _ = jax.lax.scan(held, m, c["first"] + jnp.arange(c["count"]))
        return x + m, tie, near


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _head(root, x, cfg_t, precision):
    c = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        dtype = getattr(jnp, c["dtype"])
        w = _matrix(_key(root, "lm_head"), (c["H"], c["V"]), c["H"], precision,
                    dtype)
        g = _gain(_key(root, "final_norm"), (c["H"],), dtype)
        return _act(_rms_norm(x, g, c["eps"]), precision) @ w


def padded(n: int, pad_to: int) -> int:
    """The rows a sequence of n tokens is run at: one of a few sizes, so that
    every run of a cell compiles the same few programs, and a short sequence
    does not pay for the longest (causal attention, a causal selection and a
    window keep the padding out of the real rows)."""
    for p in PADS:
        if n <= p <= pad_to:
            return p
    return pad_to


def logits_at(cfg: dict, seed: int, sequences: list[np.ndarray],
              positions: list[np.ndarray], pad_to: int,
              precision: str = "f32") -> list[np.ndarray]:
    """Full forward of each token sequence; returns, per sequence, the float32
    logits [len(positions[i]), V] at the given positions (row p holds the
    distribution of token p + 1)."""
    assert max(len(s) for s in sequences) <= pad_to
    cfg_t = tuple(sorted(dims(cfg).items()))
    root = jax.random.key(int(seed))
    out, tied, near, gaps = [], [], [], []
    dense = cfg["first_k_dense_replace"]
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    biases = [jnp.zeros((cfg["router_experts"],), jnp.float32)
              if layer < dense else _fitted_bias(root, jnp.int32(layer), cfg_t)
              for layer in range(cfg["num_hidden_layers"])]
    for seq, pos in zip(sequences, positions):
        T = padded(len(seq), pad_to)
        assert T % min(Q_BLOCK, T) == 0
        tokens = np.zeros(T, np.int32)
        tokens[:len(seq)] = seq
        x = _embed(root, jnp.asarray(tokens), cfg_t, precision)
        ties = jnp.zeros((T,), jnp.int32)
        nears = jnp.zeros((T,), jnp.int32)
        for layer in range(cfg["num_hidden_layers"]):
            x, tie, nr = _layer(root, jnp.int32(layer), x, biases[layer],
                                cfg_t, precision, layer < dense, kinds[layer])
            ties, nears = ties + tie, nears + nr
        rows = -(-len(pos) // HEAD_ROWS) * HEAD_ROWS     # few head shapes
        idx = np.zeros(rows, np.int32)
        idx[:len(pos)] = pos
        logits = np.asarray(_head(root, x[idx], cfg_t, precision)[:len(pos)])
        out.append(logits)
        pos = np.asarray(pos)
        nxt = np.minimum(pos + 1, len(seq) - 1)
        gaps.append(logits.max(-1)
                    - logits[np.arange(len(pos)), np.asarray(seq)[nxt]])
        tied.append(np.asarray(ties)[pos] > 0)
        near.append(np.asarray(nears)[pos] > 0)
    tied, near, gaps = (np.concatenate(a) for a in (tied, near, gaps))
    either = tied | near

    def widest(which):
        return float(gaps[which].max()) if which.any() else 0.0

    print(f"reference mixed_latent_moe ({precision}): of {gaps.size} sampled "
          f"positions {int(tied.sum())} have a router near-tie (8th and 9th "
          f"choice within {NEAR_TIE} in some expert layer) and "
          f"{int(near.sum())} a selection near-tie (the last position kept "
          f"and the first left out within {NEAR_TIE} of the scores' spread in "
          f"some full layer); widest gap of the sequence's next token among "
          f"either {widest(either):.5f}, among the rest "
          f"{widest(~either):.5f}", flush=True)
    return out
