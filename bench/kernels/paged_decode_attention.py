"""Work of the paged decode attention kernel: one query token per row over
that row's cached keys and values.

Counted from what the algorithm needs, not from what the kernel touches:
the keys and values of each row's live tokens, read once, and the query
and output rows.  Pages past a row's length, and the rows of idle slots,
are not counted, so a kernel that skips them reads as faster with no
recount here.
"""
from __future__ import annotations

from typing import Iterable


def flops(lengths: Iterable[int], heads: int, head_dim: int) -> float:
    """Scores (2 H D per cached token) and the weighted sum of values
    (2 H D per cached token)."""
    return float(sum(4 * heads * head_dim * int(n) for n in lengths))


def bytes_moved(lengths: Iterable[int], heads: int, kv_heads: int,
                head_dim: int, itemsize: int) -> float:
    """Keys and values of every live token, plus each live row's query and
    output."""
    lengths = [int(n) for n in lengths]
    kv = sum(2 * n * kv_heads * head_dim for n in lengths)
    qo = 2 * len(lengths) * heads * head_dim
    return float((kv + qo) * itemsize)
