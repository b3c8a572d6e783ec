"""Operations and bytes one call of the paged-attention kernel needs.

One call serves one layer of one unified step.  Counted is the work the
algorithm needs, whatever implements it:

- every valid K/V page of each kv head is read once (not once per query
  head of its group), at `kv_bits` per element;
- plus the int8 queries, the int32 output and the row's page table;
- 2 * head_dim operations per (query head, query, visible position) for
  q.k and again for p.v, over the causally visible positions only.

A row is (start, n): n query tokens at positions start .. start+n-1,
each seeing positions 0 .. its own; a decode row is (pos, 1).
"""
from __future__ import annotations

KERNEL = "paged_attention"


def visible(start: int, n: int) -> int:
    """Sum over the row's n queries of the positions each one sees."""
    return n * start + n * (n + 1) // 2


def call(rows, *, n_heads: int, n_kv_heads: int, head_dim: int,
         page_size: int, pages_per_slot: int, kv_bits: int = 8):
    """-> (ops, bytes) of one kernel call over `rows`."""
    ops = 0
    nbytes = 0
    for start, n in rows:
        ops += 4 * head_dim * n_heads * visible(start, n)
        pages = -(-(start + n) // page_size)
        nbytes += 2 * pages * n_kv_heads * head_dim * page_size * kv_bits // 8
        nbytes += n * n_heads * head_dim * (1 + 4)  # int8 q, int32 out
        nbytes += 4 * pages_per_slot  # the row's page table
    return ops, nbytes


def roofline_s(ops: int, nbytes: int, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["int8_ops_s"], nbytes / peak["hbm_bytes_s"])
