"""Decide `correct`: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample
of the requests the run finished, drawn from the seed and holding the
finished request with the most served tokens, is run through the
configuration's own reference (`harness.reference`) over its prompt
and served tokens.  At every served
token the gap by which its logit lies below the reference's best logit
at that position is read.  The number compared is the mean gap over
every served token of the sample, against the limit in
`bench/checks/<workload>.json`; the widest gap is printed beside it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness import reference


def sample(served: Dict[int, tuple], prompts: Dict[int, np.ndarray],
           seed: int, n_requests: int) -> List[int]:
    """Request ids: the finished one with the most served tokens, then
    other finished ones in an order drawn from `seed`."""
    ids = sorted(r for r, (toks, _, done) in served.items()
                 if done and toks and r in prompts)
    if not ids:
        return []
    longest = max(ids, key=lambda r: (len(served[r][0]), -r))
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    rest = [r for r in rng.permutation(ids).tolist() if r != longest]
    return [longest] + rest[:n_requests - 1]


def gaps(ref, key, dims, prompts, served, ids, length: int, *,
         bits=None) -> np.ndarray:
    """Every served token's gap below the best of reference module
    `ref`, over `ids`; with `bits`, the control's own choice at each of
    those positions."""
    out = []
    for r in ids:
        toks = np.asarray(served[r][0], np.int32)
        if bits is None:
            g = reference.served_gaps(ref, key, dims, prompts[r], toks,
                                      length=length)
        else:
            g = reference.control_gaps(ref, key, dims, prompts[r], toks,
                                       bits, length=length)
        out.append(g)
    return np.concatenate(out) if out else np.zeros(0)


def numbers(g: np.ndarray) -> dict:
    return {"mean_gap": float(g.mean()) if g.size else float("nan"),
            "widest_gap": float(g.max()) if g.size else float("nan"),
            "tokens": int(g.size)}


def judge(nums: dict, limits: dict) -> bool:
    """Every compared number at or under its limit (NaN fails)."""
    return all(nums[k] <= v for k, v in limits.items())
