"""Fixture: plain float32 forward of a Mixtral block in numpy, added to a copy
of the fixtures as a new file beside its launcher.

Per layer: RMSNorm -> grouped-query attention with split-half rotary embeddings
-> residual -> RMSNorm -> router (softmax over all experts, the top k kept and
renormalised to sum 1) -> the kept experts' SwiGLUs, weighted -> residual;
final RMSNorm and the LM head (the embedding, where tied). No cache, no
dispatch tensor, no capacity: every token reaches its k experts.

This is a TEST of the harness's seam, not a cell: the weights are the program's
own seeded draw (``moe.init_params`` over the config its launcher builds, the
one call into the program), which a cell's reference may not take. The forward
calls nothing of the program."""

from __future__ import annotations

import os

import numpy as np


def _weights(cfg: dict, seed: int) -> dict:
    import jax

    from benchmark import plugins
    from kukeon_tpu.models import moe

    beside = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    c = plugins.load("launchers", "moe_softmax_topk",
                     beside).program_config(cfg)
    tree = moe.init_params(jax.random.key(int(seed)), c)
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, heads, D]; split-half rotation at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _forward(w: dict, cfg: dict, tokens: np.ndarray) -> np.ndarray:
    """Hidden states [T, H] after the final norm."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    k_top = cfg["num_experts_per_tok"]
    t = len(tokens)
    causal = np.tril(np.ones((t, t), bool))
    x = w["embed"][tokens]
    ly = w["layers"]
    for i in range(cfg["num_hidden_layers"]):
        h = _rms(x, ly["attn_norm"][i], eps)
        q = _rope((h @ ly["wq"][i]).reshape(t, nh, d), theta)
        k = _rope((h @ ly["wk"][i]).reshape(t, nkv, d), theta)
        v = (h @ ly["wv"][i]).reshape(t, nkv, d)
        k, v = (np.repeat(a, nh // nkv, axis=1) for a in (k, v))
        s = np.einsum("qhd,thd->hqt", q, k) * d ** -0.5
        p = _softmax(np.where(causal[None], s, -np.inf))
        x = x + np.einsum("hqt,thd->qhd", p, v).reshape(t, nh * d) @ ly["wo"][i]

        h = _rms(x, ly["mlp_norm"][i], eps)
        probs = _softmax(h @ ly["router"][i])                   # [T, E]
        kept = np.argsort(-probs, -1, kind="stable")[:, :k_top]
        gate = np.take_along_axis(probs, kept, -1)
        gate = gate / gate.sum(-1, keepdims=True)
        y = np.zeros_like(x)
        for e in range(cfg["num_local_experts"]):
            mine = (kept == e)                                  # [T, k]
            rows = mine.any(-1)
            if not rows.any():
                continue
            he = h[rows]
            out = (_silu(he @ ly["w_gate"][i, e]) * (he @ ly["w_up"][i, e])) \
                @ ly["w_down"][i, e]
            y[rows] += out * (gate[rows] * mine[rows]).sum(-1, keepdims=True)
        x = x + y
    return _rms(x, w["final_norm"], eps)


def logits_at(cfg: dict, seed: int, sequences: list[np.ndarray],
              positions: list[np.ndarray], pad_to: int,
              precision: str = "f32") -> list[np.ndarray]:
    """Per sequence, the float32 logits [len(positions[i]), V] at the given
    positions (row p holds the distribution of token p + 1). ``pad_to`` is
    not needed (nothing compiles); this fixture has no lower precision."""
    if precision != "f32":
        raise ValueError(f"no control {precision!r} in this fixture")
    w = _weights(cfg, seed)
    head = w["embed"].T if cfg["tie_word_embeddings"] else w["lm_head"]
    return [(_forward(w, cfg, np.asarray(s))[np.asarray(p)] @ head)
            .astype(np.float32) for s, p in zip(sequences, positions)]
