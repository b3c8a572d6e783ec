"""Fused W8A8 matmul + requantization Pallas kernel (DESIGN.md §6).

Computes, entirely on-chip:

    acc[m, n] = sum_k x[m, k] * w[k, n]          (int8 x int8 -> int32, MXU)
    out[m, n] = clip( ((acc + b[n]) >> s0[n]) * mul[n] >> (d - s0[n]) + zp,
                      qmin, qmax ).astype(int8)                    (VPU)

i.e. paper Eq. 16 (integer-image Linear) fused with Eq. 11/13 (integer
activation via requantization).  The int32 accumulator lives in a VMEM
scratch tile and never touches HBM — on TPU v5e this is the difference
between the 394 TOPS int8 MXU path and an HBM-bound int32 spill.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation).
Block shapes default to MXU-aligned (128, 128, 128); shapes must divide
(callers pad — `ops.int8_matmul_requant` handles ragged shapes).

Static parameters (baked per call site): d, zp, qmin, qmax.  Per-channel
tables (bias, multiplier, pre-shift) stream as (bn,) blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_on_platform


def _kernel(x_ref, w_ref, b_ref, m_ref, s0_ref, o_ref, acc_ref, *,
            n_k: int, d: int, zp: int, qmin: int, qmax: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...] + b_ref[...][None, :].astype(jnp.int32)
        s0 = s0_ref[...][None, :].astype(jnp.int32)
        mul = m_ref[...][None, :].astype(jnp.int32)
        staged = jnp.right_shift(acc, s0) * mul
        out = jnp.right_shift(staged, d - s0) + zp
        o_ref[...] = jnp.clip(out, qmin, qmax).astype(jnp.int8)


def int8_matmul_requant_pallas(
    x, w, bias, mul, s0, *, d: int, zp: int = 0, qmin: int = -128,
    qmax: int = 127, bm: int = 128, bn: int = 128, bk: int = 128,
):
    """x (M, K) int8; w (K, N) int8; bias/mul/s0 (N,) int32 -> (M, N) int8.

    M, K, N must be multiples of the block shape (use ops.py for padding).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0, (
        (M, K, N), (bm, bn, bk))
    n_k = K // bk
    kern = functools.partial(
        _kernel, n_k=n_k, d=d, zp=zp, qmin=qmin, qmax=qmax
    )
    def build(interpret):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
            grid=(M // bm, N // bn, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((bn,), lambda i, j, k: (j,)),
                pl.BlockSpec((bn,), lambda i, j, k: (j,)),
                pl.BlockSpec((bn,), lambda i, j, k: (j,)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            interpret=interpret,
        )

    return pallas_on_platform(build, x, w, bias, mul, s0)
