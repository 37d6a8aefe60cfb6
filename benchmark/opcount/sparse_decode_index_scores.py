"""Operations and bytes one call of the decode indexer needs
(``kukeon_tpu/ops/sparse_attention.py`` ``decode_index_scores``), from shapes:
one query a slot against ``live_rows`` index keys in all (the slots' live rows
summed), ``heads`` index heads of ``dim``.

Bytes bound it: every live key once (``dim`` values a row), the slots' queries
and the scores out (float32 a live row). Operations: two a live row, head and
dim.
"""

from __future__ import annotations


def count(slots: float, live_rows: float, heads: int, dim: int,
          act_bytes: int = 2) -> dict:
    return {"flops": 2.0 * heads * dim * live_rows,
            "bytes": act_bytes * (live_rows * dim + slots * heads * dim)
            + 4 * live_rows}
