"""Plain float32 reference of a latent attention that selects its rows over an
expert layer with group-limited routing (the ``deepseek_v32`` block of
deepseek-ai/DeepSeek-V3.2-Exp), cut to one chip's share of an expert-parallel
deployment. With ``N`` an RMSNorm, ``R`` / ``R'`` the rotation of a 64-wide
part at the token's position under YaRN frequencies (``R`` pairs neighbours,
``R'`` split halves), ``h = N1(x)``:

    cq  = Nq(h Wq_a);  q_i = cq Wq_b -> heads of [q_nope_i | R(q_rope_i)]
    ckv | k_r = h Wkv_a;  c = Nkv(ckv),  kr = R(k_r)
    k_nope_i | v_i = c Wkv_b
    qI_j = cq WI_q -> 64 heads of 128, the first 64 under R'
    kI   = LayerNorm(h WI_k), the first 64 under R'
    w_j  = (h WI_w)_j * 64^-0.5 * 128^-0.5
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
    S_t  = the min(index_topk, t + 1) positions s <= t of largest I(t, s)
    a_i(t) = softmax over S_t of ((q_nope_i . k_nope_i(s) + R(q_rope_i) . kr(s))
                                  * 192^-0.5 * m^2),   m = 0.1 ln 40 + 1
    x += concat_i(sum_s a_i(t, s) v_i(s)) Wo
    dense layers:   x += SwiGLU(N2 x)
    expert layers:  s = sigmoid(float32(N2 x) Wr);  s' = s + b
        a group of 32 scores the sum of its two best s';  the 4 best of the 8
        groups stand;  sel = top 8 of s' among them
        g = s[sel] / sum(s[sel]) * 2.5
        x += Shared(N2 x) + sum_{e in sel, e held} g_e Expert_e(N2 x)
    logits = N_f(x) W_head

The router scores all ``router_experts``; this chip adds the part of the
experts it holds (``experts_held``); the part of absent experts is left out, as
on the chip. No cache, no absorbed form, no batching, no bf16 arithmetic: the
full forward in the expanded form, one sequence at a time, every product under
``jax.default_matmul_precision("highest")``, the index scores and the attention
in query blocks and the heads in groups so that 32768 rows fit.

It imports nothing of the program. The benchmark DEFINES the served weights: a
leaf is a seeded Gaussian under the key folded from (seed, the leaf's index in
``LEAVES``, layer, expert), of standard deviation ``fan_in ** -0.5``, rounded
to bfloat16 as the configuration states (the embedding's rows at unit
variance, so that a token's own vector and not its sequence's mean leads the
stream; norm gains 1 + 0.1 g, the LayerNorm's shift 0.1 g; router and bias
float32). The selection bias gives every seed the same load: it is fitted to
the layer's router (``selection_bias``). The program's checkpoint-less boot
follows the same recipe (``tests/bench`` pins the two).

``precision`` selects the lower-precision controls the limits have to reject
(never used by a benchmark run): "a8" int8 activations into every matrix
product and int8 cached rows (the latent row and the index key), the step
below bf16; "w4" int4 weights.

Two selections are discontinuities. Where a token's 8th and 9th expert lie
within 1e-3, or its 2048th and 2049th position within 1e-3 of the scores'
spread, bf16 arithmetic can pick the other one. Every call prints how many of
the sampled positions are such near-ties and the widest gap among them and
among the rest.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("embed", "lm_head", "final_norm", "norm1", "norm2", "wq_a",
          "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wi_q", "wi_k",
          "wi_k_gain", "wi_k_shift", "wi_w", "w_gate", "w_up", "w_down",
          "router", "bias", "s_gate", "s_up", "s_down", "e_gate", "e_up",
          "e_down")
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
PADS = (4096, 8192, 16384, 32768, 65536, 131072)


def dims(cfg: dict) -> dict:
    first, count = cfg["experts_held"]
    y = cfg["rope_scaling"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "NH": cfg["num_attention_heads"], "Q": cfg["q_lora_rank"],
            "R": cfg["kv_lora_rank"], "Dn": cfg["qk_nope_head_dim"],
            "Dr": cfg["qk_rope_head_dim"], "Dv": cfg["v_head_dim"],
            "Hi": cfg["index_n_heads"], "Di": cfg["index_head_dim"],
            "topk": cfg["index_topk"], "E": cfg["router_experts"],
            "K": cfg["num_experts_per_tok"], "first": first, "count": count,
            "groups": cfg["n_group"], "kept": cfg["topk_group"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]), "factor": float(y["factor"]),
            "orig": int(y["original_max_position_embeddings"]),
            "fast": float(y["beta_fast"]), "slow": float(y["beta_slow"]),
            "mscale": float(y["mscale"]),
            "served": cfg["max_position_embeddings"],
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


def _chosen(biased, k, groups, kept):
    """The k experts of largest biased score [T, E] among those of the
    ``kept`` best groups: a group of E / groups neighbours scores the sum of
    its two best."""
    if groups > 1:
        T, E = biased.shape
        by_group = biased.reshape(T, groups, E // groups)
        score = jax.lax.top_k(by_group, 2)[0].sum(axis=-1)
        best = jax.lax.top_k(score, kept)[1]
        stands = (best[:, :, None] == jnp.arange(groups)).any(axis=1)
        biased = jnp.where(stands[:, :, None], by_group, -jnp.inf
                           ).reshape(T, E)
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
        sel = _chosen(s + b, c["K"], c["groups"], c["kept"])[1][:, :c["K"]]
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


def inv_freq(c: dict):
    """YaRN, as the published code computes it: a pair that turns more than
    beta_fast times over the original context keeps its frequency, one that
    turns less than beta_slow times has it divided by the factor, a linear
    ramp between; plain frequencies where the served context does not pass
    the original one."""
    d = c["Dr"]
    freqs = 1.0 / (c["theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if c["served"] <= c["orig"]:
        return freqs

    def pair_of(turns):
        return (d * math.log(c["orig"] / (turns * 2 * math.pi))
                / (2 * math.log(c["theta"])))

    low = max(math.floor(pair_of(c["fast"])), 0)
    high = min(math.ceil(pair_of(c["slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return freqs / c["factor"] * ramp + freqs * (1 - ramp)


def softmax_scale(c: dict) -> float:
    scale = (c["Dn"] + c["Dr"]) ** -0.5
    if c["served"] > c["orig"]:
        m = 0.1 * c["mscale"] * math.log(c["factor"]) + 1.0
        scale *= m * m
    return scale


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


@functools.partial(jax.jit, static_argnames=("cfg_t", "precision", "dense"))
def _layer(root, layer, x, bias, cfg_t, precision, dense):
    """One block over x [T, H]; ``layer`` (traced) keys its weights, ``bias``
    [E] is its selection bias. Returns (x', router near-tie flags [T],
    selection near-tie flags [T])."""
    c = dict(cfg_t)
    H, NH, Q, R = c["H"], c["NH"], c["Q"], c["R"]
    Dn, Dr, Dv, Hi, Di = c["Dn"], c["Dr"], c["Dv"], c["Hi"], c["Di"]
    Dk, eps = Dn + Dr, c["eps"]
    dtype = getattr(jnp, c["dtype"])
    T = x.shape[0]
    pos = jnp.arange(T)
    freqs = inv_freq(c)

    def mat(name, shape, fan_in, expert=None):
        return _matrix(_key(root, name, layer, expert), shape, fan_in,
                       precision, dtype)

    def gain(name, shape):
        return _gain(_key(root, name, layer), shape, dtype)

    with jax.default_matmul_precision("highest"):
        h = _act(_rms_norm(x, gain("norm1", (H,)), eps), precision)
        cq = _act(_rms_norm(h @ mat("wq_a", (H, Q), H), gain("q_norm", (Q,)),
                            eps), precision)
        kv = h @ mat("wkv_a", (H, R + Dr), H)
        latent = _rms_norm(kv[:, :R], gain("kv_norm", (R,)), eps)
        kr = _rotate(kv[:, None, R:], pos, freqs, True)[:, 0]
        # the indexer
        qi = (cq @ mat("wi_q", (Q, Hi * Di), Q)).reshape(T, Hi, Di)
        qi = jnp.concatenate([_rotate(qi[..., :Dr], pos, freqs, False),
                              qi[..., Dr:]], axis=-1)
        ki = h @ mat("wi_k", (H, Di), H)
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                                + LN_EPS)
        ki = ki * gain("wi_k_gain", (Di,)) + _gain(
            _key(root, "wi_k_shift", layer), (Di,), dtype, SHIFT_STD, 0.0)
        ki = jnp.concatenate(
            [_rotate(ki[:, None, :Dr], pos, freqs, False)[:, 0], ki[:, Dr:]],
            axis=-1)
        wts = (h @ mat("wi_w", (H, Hi), H)) * (Hi ** -0.5 * Di ** -0.5)
        if precision == "a8":       # an int8 cache holds these rows
            latent, kr, ki = (_act(a, precision) for a in (latent, kr, ki))
            qi = _act(qi, precision)
        mask, near = _selection(qi, wts, ki, c["topk"])

        # the expanded form, a group of heads at a time
        G = min(HEAD_GROUP, NH)
        wq_b = mat("wq_b", (Q, NH * Dk), Q).reshape(Q, NH // G, G * Dk)
        wkv_b = mat("wkv_b", (R, NH * (Dn + Dv)), R).reshape(
            R, NH // G, G * (Dn + Dv))
        scale = softmax_scale(c)

        def group(g):
            q = (cq @ wq_b[:, g]).reshape(T, G, Dk)
            q = jnp.concatenate(
                [q[..., :Dn], _rotate(q[..., Dn:], pos, freqs, True)], -1)
            kvx = (_act(latent, precision) @ wkv_b[:, g]).reshape(
                T, G, Dn + Dv)
            k = jnp.concatenate(
                [kvx[..., :Dn], jnp.broadcast_to(kr[:, None], (T, G, Dr))],
                axis=-1)
            return _attention(_act(q, precision), k, kvx[..., Dn:], mask,
                              scale)

        o = jax.lax.map(group, jnp.arange(NH // G))         # [NG, T, G, Dv]
        o = jnp.moveaxis(o, 0, 1).reshape(T, NH * Dv)
        x = x + _act(o, precision) @ mat("wo", (NH * Dv, H), NH * Dv)

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
        ranked, sel = _chosen(s + bias, K, c["groups"], c["kept"])
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
    does not pay for the longest (causal attention and a causal selection
    keep the padding out of the real rows)."""
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
                                cfg_t, precision, layer < dense)
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

    print(f"reference sparse_latent_moe ({precision}): of {gaps.size} sampled "
          f"positions {int(tied.sum())} have a router near-tie (8th and 9th "
          f"choice within {NEAR_TIE} in some expert layer) and "
          f"{int(near.sum())} a selection near-tie (the last position kept "
          f"and the first left out within {NEAR_TIE} of the scores' spread in "
          f"some layer); widest gap of the sequence's next token among "
          f"either {widest(either):.5f}, among the rest "
          f"{widest(~either):.5f}", flush=True)
    return out
