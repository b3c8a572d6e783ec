"""Dropless grouped expert kernel: every routed (token, expert) pair of
an integer MoE layer, in one Pallas call (named ``moe_experts``).

Per pair the kernel computes the expert's whole SwiGLU in the integer
dataflow of `layers/moe.QMoE` (W8A8, per-(expert, channel) requant
tables, the gate's int8 SiLU table):

    g   = x . Wg[e]                  int8 x int8 -> int32
    u   = x . Wu[e]
    h   = RQ_h((LUT(RQ_g(g)) - zp_g) * RQ_u(u))     int8
    out = RQ_o(h . Wd[e])            int8, the expert's output image

Pairs are sorted by expert outside the kernel, so each expert's rows
are one contiguous run of the sorted rows.  The grid walks *visits*: a
visit is one (row tile, expert) pair whose run meets the tile, in
expert order, so an expert's three matrices are copied into VMEM once
per call however many tiles its run spans, and a tile that holds the
end of one run and the start of the next is visited once per expert
(its output block stays in VMEM between the two, and each visit writes
only its own expert's rows).  The grid has room for the most visits a
call can need (tiles + experts - 1); visits past the real count repeat
the last real one's block indices, so they copy nothing, and compute
nothing.  Pairs routed nowhere (expert id == n_experts: padding, and
rows parked by the engine) sort last and are never visited; their
output rows are left as they are, and the caller weights them by 0.

The SiLU table is read through a select tree over its 256 entries held
in SMEM (no gather), walked depth first so that at most one select per
level is live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_on_platform

TILE = 32  # rows per tile: one int8 sublane tile
VMEM_LIMIT = 64 * 1024 * 1024  # three double-buffered expert matrices

# scalar-table slots in the SMEM operand
(G_D, G_ZP, U_D, U_ZP, H_M, H_D, H_S0, H_LO, H_HI, H_ZP, O_D, O_ZP,
 ZP_G) = range(13)


def _requant(acc, tab, d, zp):
    """`core.requant.apply_rqt` with per-channel rows m, s0, lo, hi of
    `tab` (4, C) broadcast over the rows of `acc` (R, C)."""
    m, s0, lo, hi = (tab[i:i + 1] for i in range(4))
    staged = jnp.right_shift(jnp.clip(acc, lo, hi), s0) * m
    return jnp.clip(jnp.right_shift(staged, d - s0) + zp, -128, 127)


def _lut(idx, lut_ref, lo: int, n: int):
    """lut_ref[idx] for idx in [lo, lo + n), n a power of two."""
    if n == 1:
        return lut_ref[lo]
    half = n // 2
    a = _lut(idx, lut_ref, lo, half)
    b = _lut(idx, lut_ref, lo + half, half)
    return jnp.where((idx & half) != 0, b, a)


def _kernel(tile_ref, exp_ref, nvis_ref, glo_ref, ghi_ref, sc_ref, lut_ref,
            x_ref, wg_ref, wu_ref, wd_ref, gt_ref, ut_ref, ot_ref, o_ref,
            *, tm: int, wide_dots: bool):
    v = pl.program_id(0)

    def dot(a, b):
        if wide_dots:  # the interpreter's build (kernels/paged_attention)
            a, b = a.astype(jnp.int32), b.astype(jnp.int32)
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(v < nvis_ref[0])
    def _():
        e = exp_ref[v]
        x = x_ref[...]
        s_pre = _requant(dot(x, wg_ref[0]), gt_ref[0], sc_ref[G_D],
                         sc_ref[G_ZP])
        s_g = _lut(s_pre + 128, lut_ref, 0, 256)
        s_u = _requant(dot(x, wu_ref[0]), ut_ref[0], sc_ref[U_D],
                       sc_ref[U_ZP])
        prod = (s_g - sc_ref[ZP_G]) * s_u
        staged = jnp.right_shift(
            jnp.clip(prod, sc_ref[H_LO], sc_ref[H_HI]), sc_ref[H_S0])
        s_h = jnp.clip(
            jnp.right_shift(staged * sc_ref[H_M], sc_ref[H_D] - sc_ref[H_S0])
            + sc_ref[H_ZP], -128, 127)
        s_o = _requant(dot(s_h.astype(jnp.int8), wd_ref[0]), ot_ref[0],
                       sc_ref[O_D], sc_ref[O_ZP])
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= glo_ref[e]) & (row < ghi_ref[e])
        o_ref[...] = jnp.where(mine, s_o, o_ref[...].astype(jnp.int32)
                               ).astype(jnp.int8)


def _channel_tables(rqt, n_experts: int, width: int):
    """A per-(expert, channel) requant tree -> (E, 4, C) int32 rows
    m, s0, lo, hi."""
    return jnp.stack([
        jnp.broadcast_to(jnp.asarray(rqt[k], jnp.int32), (n_experts, width))
        for k in ("m", "s0", "lo", "hi")], axis=1)


def _scalars(t):
    g, u, h, o = t["g_rqt"], t["u_rqt"], t["h_rqt"], t["o_rqt"]
    vals = [g["d"], g["zp"], u["d"], u["zp"], h["m"], h["d"], h["s0"],
            h["lo"], h["hi"], h["zp"], o["d"], o["zp"], t["zp_g"]]
    return jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in vals])


def group_visits(experts_sorted, n_experts: int, n_tiles: int, tm: int):
    """From the pairs' expert ids in sorted order (E for none), each
    expert's row range [lo, hi) and the grid's visits: (tile, expert)
    per visit, real ones first, then the last real one repeated; and
    how many are real."""
    e_ids = jnp.arange(n_experts, dtype=jnp.int32)
    hi = jnp.sum(experts_sorted[None, :] <= e_ids[:, None], axis=1,
                 dtype=jnp.int32)
    lo = jnp.concatenate([jnp.zeros((1,), jnp.int32), hi[:-1]])
    first, last = lo // tm, (hi - 1) // tm
    n_vis = jnp.where(hi > lo, last - first + 1, 0)
    ends = jnp.cumsum(n_vis)
    total = ends[-1]
    n_grid = n_tiles + n_experts - 1
    v = jnp.minimum(jnp.arange(n_grid, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    exp_v = jnp.minimum(
        jnp.sum(ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        n_experts - 1)
    tile_v = jnp.clip(first[exp_v] + v - (ends - n_vis)[exp_v], 0,
                      n_tiles - 1)
    return lo, hi, tile_v, exp_v, total.reshape(1)


def moe_experts(x, experts, t):
    """x (T, d) int8 rows, experts (T, k) int32 expert ids (n_experts:
    none), t the layer's `QMoE` tables -> (T, k, d) int8: each pair's
    expert output image (rows of pairs routed nowhere are undefined).
    """
    T, d = x.shape
    k, tm = experts.shape[1], TILE
    E, _, f = t["wg_q"].shape
    P = T * k
    n_tiles = -(-P // tm)
    M = n_tiles * tm
    e_flat = experts.reshape(P).astype(jnp.int32)
    e_sorted, order = jax.lax.sort(
        (e_flat, jnp.arange(P, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    lo, hi, tile_v, exp_v, total = group_visits(e_sorted, E, n_tiles, tm)
    x_sorted = jnp.take(x, order // k, axis=0)
    if M > P:
        x_sorted = jnp.pad(x_sorted, ((0, M - P), (0, 0)))
    lut = jnp.asarray(t["g_lut"], jnp.int32).reshape(-1)
    operands = (
        tile_v, exp_v, total, lo, hi, _scalars(t), lut,
        x_sorted, t["wg_q"], t["wu_q"], t["wd_q"],
        _channel_tables(t["g_rqt"], E, f),
        _channel_tables(t["u_rqt"], E, f),
        _channel_tables(t["o_rqt"], E, d),
    )

    def rows(v, tile, *_):
        return tile[v], 0

    def expert(v, tile, exp, *_):
        return exp[v], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_tiles + E - 1,),
        in_specs=[
            pl.BlockSpec((tm, d), rows),
            pl.BlockSpec((1, d, f), expert),
            pl.BlockSpec((1, d, f), expert),
            pl.BlockSpec((1, f, d), expert),
            pl.BlockSpec((1, 4, f), expert),
            pl.BlockSpec((1, 4, f), expert),
            pl.BlockSpec((1, 4, d), expert),
        ],
        out_specs=pl.BlockSpec((tm, d), rows),
    )

    def build(interpret):
        return pl.pallas_call(
            functools.partial(_kernel, tm=tm, wide_dots=interpret),
            out_shape=jax.ShapeDtypeStruct((M, d), jnp.int8),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="moe_experts",
        )

    out = pallas_on_platform(build, *operands)
    where = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32))
    return jnp.take(out, where, axis=0).reshape(T, k, d)
