"""Integer operations one unified step of a dense decoder needs.

Matrix products: 2 operations per weight per token that passes through
it, for every layer's q, k, v, o, gate, up and down projections; the
vocabulary projection once per row, at the position whose next token
the step produces.  Attention: as `counts/paged_attention.py`, at each
row's true context.  Norms, rotary and the softmax are not counted.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path


def _attention():
    path = Path(__file__).with_name("paged_attention.py")
    spec = importlib.util.spec_from_file_location("counts_pa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PA = _attention()


def layer_weights(dims) -> int:
    d, hd = dims.d_model, dims.head_dim
    attn = d * (dims.n_heads + 2 * dims.n_kv_heads) * hd
    attn += dims.n_heads * hd * d
    return attn + 3 * d * dims.d_ff


def step_ops(rows, dims) -> int:
    """rows: (start, n) per real row of the step."""
    tokens = sum(n for _, n in rows)
    ops = 2 * tokens * layer_weights(dims) * dims.n_layers
    ops += 2 * len(rows) * dims.d_model * dims.vocab
    ops += dims.n_layers * sum(
        4 * dims.head_dim * dims.n_heads * PA.visible(s, n)
        for s, n in rows)
    return ops
