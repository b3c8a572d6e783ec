"""Operations and bytes one call of the grouped expert kernel
(`moe_experts`) needs: one MoE layer of one unified step, over the
step's real tokens, each routed to `top_k` of `n_experts` experts.

- 2 * 3 * d_model * d_expert operations per routed (token, expert)
  pair: its gate, up and down products;
- the three int8 matrices of each expert that gets a pair, read once:
  min(n_experts, tokens * top_k) experts, the most that can be hit
  (exact once tokens * top_k >= n_experts and the router spreads);
- per pair, its int8 input row and its int8 output row.
"""
from __future__ import annotations

KERNEL = "moe_experts"


def call(tokens: int, *, d_model: int, d_expert: int, n_experts: int,
         top_k: int):
    """-> (ops, bytes) of one kernel call over `tokens` real tokens."""
    pairs = tokens * top_k
    ops = 2 * 3 * d_model * d_expert * pairs
    nbytes = 3 * d_model * d_expert * min(n_experts, pairs)
    nbytes += 2 * pairs * d_model  # int8 in, int8 out
    return ops, nbytes


def roofline_s(ops: int, nbytes: int, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["int8_ops_s"], nbytes / peak["hbm_bytes_s"])
