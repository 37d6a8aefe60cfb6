"""Operations and bytes one call of the prefill attention under a selection
needs (``kukeon_tpu/ops/sparse_attention.py`` ``masked_attention``), from
shapes: ``heads`` heads of one group, ``queries`` rows against ``keys``
positions of which ``selected`` (query, key) pairs are attended.

Operations: two a SELECTED pair, head and width of q k^T and of p v. A kernel
that runs every causal pair under a mask does more than that and reads a low
share: the selection is what the algorithm needs. Bytes: q, k, v in and the
output out once, and the mask at a byte a (query, key).
"""

from __future__ import annotations


def count(heads: int, queries: float, keys: float, selected: float,
          qk_dim: int, v_dim: int, act_bytes: int = 2) -> dict:
    return {"flops": 2.0 * heads * selected * (qk_dim + v_dim),
            "bytes": act_bytes * heads * (queries * (qk_dim + v_dim)
                                          + keys * (qk_dim + v_dim))
            + queries * keys}


def selected_pairs(first: float, queries: float, topk: int) -> float:
    """Pairs the rows ``first .. first + queries - 1`` attend: row t keeps
    min(topk, t + 1) positions."""
    total = 0.0
    for a, b in ((first, first + queries),):
        short = max(0.0, min(b, topk) - min(a, topk))        # rows under topk
        lo = min(a, topk)
        total += short * (2 * lo + short + 1) / 2.0 + (b - a - short) * topk
    return total
