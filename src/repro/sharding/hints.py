"""Activation sharding hints — layer-level with_sharding_constraint.

Layers call `hint(x, kind)`; under a profile (installed by lower_cell /
train loop via `use_profile(mesh)`) this pins the batch/heads/mlp axes so
GSPMD keeps giant intermediates (attention probs, FFN hidden) sharded.
Outside a profile (CPU unit tests) it is a no-op.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


def _mesh():
    return getattr(_STATE, "mesh", None)


def profile_mesh():
    """The active profile's mesh (None outside a profile) — layer code
    that needs more than a constraint (e.g. the shard_map around the
    paged-attention kernel, kernels/paged_attention.py) reads it here
    instead of growing a mesh parameter through every signature."""
    return _mesh()


@contextlib.contextmanager
def use_profile(mesh):
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.mesh = prev


# kind -> list of candidate (batch_dim, model_dim) layouts; the first
# whose dims divide the mesh is used (e.g. probs fall back to sequence
# sharding when n_heads doesn't divide the model axis).
_KINDS = {
    "act_bsd": [(0, 1)],        # (B, S, d): sequence-parallel residual
    "act_bhsd": [(0, 1), (0, 2)],   # (B, H, S, hd): heads, else seq
    "probs": [(0, 1), (0, 2)],      # (B, H, S, T): heads, else q-seq
    "probs_dec": [(0, 3)],          # decode: keep kv-sequence sharding
    "ffn_h": [(0, 2), (0, 1)],      # (B, S, f): hidden, else seq
    "moe_ecd": [(0, 1)],        # (G, E, C, d): groups on data, E on model
    "moe_ecf": [(0, 1)],        # (G, E, C, f): expert hidden, same layout
    "moe_comb": [(0, 3)],       # (G, Gs, k, d): combine, d on model
    "logits": [(0, 2), (0, 1)],  # (B, S, V): vocab, else seq
    "ssm_ch": [(0, 2)],         # (B, L, di|H, ...): channels/heads on model
    "ssm_small": [(0, None)],   # (B, L, ds) B/C tensors: replicated
    "ssm_h": [(0, 1)],          # scan carry (B, di|H, ...): ch on model
    "acc_seq": [(0, 1)],        # int32 accumulator (B, L, d): L on model
                                # => reduce-scatter + local int8 requant +
                                # int8 all-gather instead of int32 AR
    "ssm_u": [(0, 2)],          # (B, L, di, ds) mamba1 chunk tensors
    "ssm_u2": [(0, 2)],         # (B, L, H, P, ds) mamba2 chunk tensors
    "batch0": [(0, None)],      # shard dim 0 on (pod, data) only
    "act_bs_only": [(0, None)],  # residual without seq sharding (MoE
                                 # blocks: avoids the SP<->EP reshard)
    "kv_heads": [(None, 1)],    # serving KV cache (B, K, T, hd) or page
                                # pool (P, K, hd, ps): kv heads on model,
                                # batch/pages replicated — keeps the
                                # arena's layout pinned through the
                                # per-slot write and the paged gather
                                # (DESIGN.md §Serving ¶Multi-device)
}


def _divides(shape, dim, axes, sizes):
    if dim is None:
        return True
    n = int(np.prod([sizes[a] for a in (
        axes if isinstance(axes, tuple) else (axes,))]))
    return shape[dim] % n == 0


def hint(x, kind: str):
    mesh = _mesh()
    if mesh is None:
        return x

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = ("pod", "data") if "pod" in mesh.axis_names else "data"
    chosen = None
    for b_ax, m_ax in _KINDS[kind]:
        ok_b = b_ax is None or (b_ax < x.ndim and _divides(
            x.shape, b_ax, batch, sizes))
        ok_m = m_ax is None or (m_ax < x.ndim and _divides(
            x.shape, m_ax, "model", sizes))
        if ok_b and ok_m:
            chosen = (b_ax, m_ax)
            break
    if chosen is None:
        return x
    b_ax, m_ax = chosen
    spec = [None] * x.ndim
    if b_ax is not None:
        spec[b_ax] = batch
    if m_ax is not None:
        spec[m_ax] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


import numpy as np  # noqa: E402
