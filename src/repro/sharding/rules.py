"""Logical-axis sharding rules: param-tree paths -> PartitionSpec.

2-D FSDP x TP layout (DESIGN.md §Sharding):
  batch           -> ("pod","data")    activations / tokens
  vocab/heads/mlp/experts -> "model"   tensor & expert parallelism
  embed (weight d_model dim) -> "data" FSDP weight sharding
  seq (kv cache)  -> "model"           sequence-sharded KV at 32k-500k

Rules are matched against the JOINED PARAM PATH (substring match, first
hit wins), then left-padded with None for stacked-layer leading dims.
This path-based mapping covers float params, FQ qstate, ID integer
tables, and optimizer moment trees (which reuse param paths) with one
rule set — no per-layer axes plumbing.

Serving cache arenas (repro.serving, DESIGN.md §Serving ¶Multi-device)
use the STRUCTURAL rules at the bottom instead of path matching: the
arenas discover each cache leaf's batch/sequence axis, and
`arena_leaf_spec` maps that to "kv heads on the model axis, everything
else replicated" — GQA-aware (it is the KV-head axis that shards, so a
mesh wider than n_kv_heads degrades to replication rather than
splitting a head) and layout-agnostic (contiguous slot rows and paged
pools share one rule because both keep the head axis just before the
sequence axis).  One rule set covers every chunked-engine dispatch:
the unified prefill+decode step (DESIGN.md §Serving ¶Unified
attention kernel) consumes the same arena tree under the same specs,
and inside it the paged-attention kernel's shard_map splits queries
(B, H, S, hd) along H on "model" against pools split along K — the
query heads of a group ride with their kv head, so per-shard S-wide
chunk rows need no cross-shard exchange.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# (path-regex, base spec in logical axes). First match wins.
_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # ---- embeddings / head ----
    (r"embed.*table", ("model", "data")),        # (vocab, d)
    (r"head.*w", ("data", "model")),             # (d, vocab)
    (r"head.*b_q", ("model",)),
    # ---- MoE experts (E, d, f) / (E, f, d); router (d, E) ----
    (r"moe.*router.*w", ("data", None)),
    (r"(moe|segments).*w[gud](_q)?$", None),     # resolved by rank below
    # ---- attention ----
    (r"attn.*wo.*w", ("model", "data")),         # (H*hd, d)
    (r"attn.*w[qkv].*w", ("data", "model")),     # (d, H*hd)
    (r"attn.*w[qkv].*b_q", ("model",)),
    (r"attn.*wo.*b_q", ("data",)),
    # ---- dense mlp ----
    (r"mlp.*wd.*w", ("model", "data")),          # (f, d)
    (r"mlp.*w[gu].*w", ("data", "model")),       # (d, f)
    (r"mlp.*w[gu].*b_q", ("model",)),
    (r"mlp.*wd.*b_q", ("data",)),
    # ---- ssm ----
    (r"(core|mamba).*in_proj.*w", ("data", "model")),
    (r"(core|mamba).*out_proj.*w", ("model", "data")),
    (r"(core|mamba).*x_proj.*w", ("model", None)),
    (r"(core|mamba).*dt_proj.*w", (None, "model")),
    (r"(core|mamba).*in_proj.*b_q", ("model",)),
    (r"(core|mamba).*out_proj.*b_q", ("data",)),
    (r"conv_w", (None, "model")),
    (r"A_log", ("model", None)),
    (r"A$", ("model", None)),
    # ---- per-channel requant tables follow their producer's out axis ----
    (r"attn.*(q_rqt|k_rqt|v_rqt)", ("model",)),
    (r"(u_rqt|h_rqt|g_rqt|o_rqt)", ("model",)),
    (r"(xz_rqt|p_rqt|xdb_rqt|conv_rqt)", ("model",)),
)


def _logical_to_mesh(axis: Optional[str], mesh) -> Optional[object]:
    if axis is None:
        return None
    if axis == "data":
        return "data"
    if axis == "model":
        return "model"
    if axis == "batch":
        return ("pod", "data") if "pod" in mesh.axis_names else "data"
    raise ValueError(axis)


def _expert_spec(ndim_base: int):
    # (E, d, f) -> experts on model, d on data; (E, f, d) handled same
    return ("model", "data", None) if ndim_base == 3 else ("data", "model")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (jit arg
    shardings require exact divisibility; e.g. vocab=49155 vs 16)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = int(np.prod([sizes[a] for a in axes]))
        out.append(ax if shape[i] % n == 0 else None)
    return P(*out)


def spec_for_path(path, leaf, mesh) -> P:
    """PartitionSpec for one param leaf (handles stacked leading dims)."""
    ps = _path_str(path)
    ndim = np.ndim(leaf)
    if ndim == 0:
        return P()
    for pattern, base in _RULES:
        if re.search(pattern, ps):
            if base is None:  # expert tensors: rank-dependent
                base = _expert_spec(3) if ndim >= 3 else ("data", "model")
            base = tuple(_logical_to_mesh(a, mesh) for a in base)
            n_lead = ndim - len(base)
            if n_lead < 0:
                # table collapsed below rule rank (e.g. scalar m) — replicate
                return P()
            # never shard tiny leading/stacked dims
            spec = P(*((None,) * n_lead + base))
            return sanitize_spec(spec, np.shape(leaf), mesh)
    return P()  # replicate by default (norm gains, luts, scalars)


def params_sharding(params, mesh, *, weight_stationary: bool = False):
    """Pytree of NamedShardings matching `params`.

    weight_stationary: drop the FSDP "data" axis from weight specs
    (replicate across data) — the serving-side layout where weights stay
    put and only activations move (§Perf hillclimb A)."""
    def one(path, leaf):
        spec = spec_for_path(path, leaf, mesh)
        if weight_stationary:
            spec = P(*tuple(None if ax == "data" else ax for ax in spec))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_spec(mesh, ndim: int, batch_axis: int = 0, shape=None) -> P:
    """Tokens/activations: batch dim over ("pod","data")."""
    b = _logical_to_mesh("batch", mesh)
    spec = [None] * ndim
    spec[batch_axis] = b
    spec = P(*spec)
    if shape is not None:
        spec = sanitize_spec(spec, shape, mesh)
    return spec


def cache_spec(mesh, ndim: int) -> P:
    """KV caches (..., B, K|heads, S, hd): batch over (pod, data),
    sequence (axis -2) over model — sequence-sharded KV."""
    b = _logical_to_mesh("batch", mesh)
    spec = [None] * ndim
    spec[-4] = b       # batch
    spec[-2] = "model"  # sequence
    return P(*spec)


# ---------------------------------------------------------------------------
# serving-arena cache rules (structural, not path-based)
# ---------------------------------------------------------------------------


def kv_head_axis(batch_axis: int, seq_axis) -> Optional[int]:
    """KV-head axis of an attention cache leaf, or None.

    Every attention cache layout the model zoo produces keeps the head
    axis immediately BEFORE the sequence axis — (..., B, K, T, hd) for
    contiguous slot rows.  Paged pools (..., n_pages + 1, K, hd,
    page_size) move the page_size axis last, but the arena's structural
    probe reports the contiguous template's (batch_axis, seq_axis)
    pair for them, so the same rule lands on K.  Leaves with no
    sequence axis (SSM recurrent state) have no head axis to shard.

    Int4-packed pools (DESIGN.md §Serving ¶Sub-8-bit KV) only halve
    the hd axis — the head axis stays where it was, so packed pools
    shard exactly like int8 ones: only
    the kv-head axis splits, nibble pairs never straddle a shard.
    """
    if seq_axis is None:
        return None
    h_ax = seq_axis - 1
    return h_ax if h_ax > batch_axis else None


def arena_leaf_spec(shape, batch_axis: int, seq_axis, mesh) -> P:
    """PartitionSpec for one serving-arena cache leaf: KV heads on the
    mesh "model" axis, everything else replicated.

    Replication is deliberate for the non-KV leaves (DESIGN.md §Serving
    ¶Multi-device): the page table and per-slot metadata are tiny int32
    host mirrors every shard needs in full, and the SSM recurrent state
    is per-slot, not a KV cache.  `sanitize_spec` degrades a KV leaf to
    replication when the model axis does not divide n_kv_heads — a
    GQA-aware fallback, never a partial head split."""
    h_ax = kv_head_axis(batch_axis, seq_axis)
    if h_ax is None:
        return P()
    spec = [None] * len(shape)
    spec[h_ax] = "model"
    return sanitize_spec(P(*spec), shape, mesh)


def arena_shardings(mesh, shapes, batch_axes, seq_axes):
    """NamedShardings for a serving arena's cache leaves (leaf-list
    aligned with the arena's flattened pytree)."""
    return [
        NamedSharding(mesh, arena_leaf_spec(s, b, q, mesh))
        for s, b, q in zip(shapes, batch_axes, seq_axes)
    ]


def caches_sharding(caches, mesh):
    """Heuristic cache sharding: 4-D+ trailing (B,K,S,hd) -> seq-sharded;
    3-D SSM states -> batch-sharded only (states are small)."""
    def one(path, leaf):
        ps = _path_str(path)
        nd = np.ndim(leaf)
        shape = np.shape(leaf) if not hasattr(leaf, "shape") else leaf.shape
        if nd >= 4 and ("k" in ps.split("/")[-1] or "v" in ps.split("/")[-1]):
            return NamedSharding(
                mesh, sanitize_spec(cache_spec(mesh, nd), shape, mesh))
        b = _logical_to_mesh("batch", mesh)
        spec = [None] * nd
        if nd >= 3:
            spec[-3] = b
        return NamedSharding(mesh, sanitize_spec(P(*spec), shape, mesh))

    return jax.tree_util.tree_map_with_path(one, caches)
