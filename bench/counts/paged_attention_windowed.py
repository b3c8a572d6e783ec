"""Operations and bytes one call of the paged-attention kernel needs on
a layer with a sliding window (`window` W: the query at position i sees
keys j with i - W < j <= i).  The terms of `counts/paged_attention.py`,
with each query's visible positions, and the pages read, capped at the
window; `window=None` gives that module's numbers exactly.

A row is (start, n): n query tokens at positions start .. start+n-1.
The pages read are those that hold a position some query of the row
sees: from the one holding start - W + 1 (or 0) to the one holding
start + n - 1.
"""
from __future__ import annotations

KERNEL = "paged_attention"


def visible(start: int, n: int, window=None) -> int:
    """Sum over the row's n queries of the positions each one sees."""
    full = n * start + n * (n + 1) // 2
    if window is None:
        return full
    a = start + 1  # positions query 0 sees without a window
    m = min(max(window - a + 1, 0), n)  # queries the window does not cap
    return m * a + m * (m - 1) // 2 + (n - m) * window


def call(rows, *, n_heads: int, n_kv_heads: int, head_dim: int,
         page_size: int, pages_per_slot: int, kv_bits: int = 8,
         window=None):
    """-> (ops, bytes) of one kernel call over `rows`."""
    ops = 0
    nbytes = 0
    for start, n in rows:
        ops += 4 * head_dim * n_heads * visible(start, n, window)
        first = 0 if window is None else max(start - window + 1, 0)
        pages = (start + n - 1) // page_size - first // page_size + 1
        nbytes += 2 * pages * n_kv_heads * head_dim * page_size * kv_bits // 8
        nbytes += n * n_heads * head_dim * (1 + 4)  # int8 q, int32 out
        nbytes += 4 * pages_per_slot  # the row's page table
    return ops, nbytes


def roofline_s(ops: int, nbytes: int, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["int8_ops_s"], nbytes / peak["hbm_bytes_s"])
