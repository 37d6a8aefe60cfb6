"""Operations and bytes one call of the prefill selection needs
(``kukeon_tpu/ops/sparse_attention.py`` ``select_rows``), from shapes:
``queries`` rows of one prompt scored against the positions at or before them,
``pairs`` (query, key) pairs in all, over ``heads`` index heads of ``dim``.

    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s));  keep the topk best of a row

Operations: two a pair, head and dim (the products; the relu, the weighting and
the search for the topk-th score are vector work, which the matrix peak does
not bound). Bytes, what has to cross HBM once: the queries and their weights
in, every key once, and the mask out at a byte a (query, key of the prompt):
the scores themselves never have to.
"""

from __future__ import annotations


def count(queries: float, keys: float, pairs: float, heads: int, dim: int,
          act_bytes: int = 2) -> dict:
    return {"flops": 2.0 * heads * dim * pairs,
            "bytes": act_bytes * (queries * heads * dim + keys * dim)
            + 4 * queries * heads + queries * keys}
