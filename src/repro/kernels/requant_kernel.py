"""Standalone requantization Pallas kernel (Eq. 13, staged form).

Pure VPU elementwise multiply-shift on an int32 tensor with per-channel
tables — the epilogue used by integer Adds and norm exits when they are
not already fused into a matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_on_platform


def _kernel(q_ref, m_ref, s0_ref, lo_ref, hi_ref, o_ref, *, d: int,
            zp: int, qmin: int, qmax: int):
    q = q_ref[...]
    m = m_ref[...][None, :]
    s0 = s0_ref[...][None, :]
    lo = lo_ref[...][None, :]
    hi = hi_ref[...][None, :]
    q = jnp.clip(q, lo, hi)
    staged = jnp.right_shift(q, s0) * m
    out = jnp.right_shift(staged, d - s0) + zp
    o_ref[...] = jnp.clip(out, qmin, qmax).astype(jnp.int8)


def requant_pallas(
    q,
    m,
    s0,
    lo,
    hi,
    *,
    d: int,
    zp: int = 0,
    qmin: int = -128,
    qmax: int = 127,
    bm: int = 256,
):
    """q (M, N) int32; m/s0/lo/hi (N,) int32 -> (M, N) int8."""
    M, N = q.shape
    assert M % bm == 0, (M, bm)
    kern = functools.partial(_kernel, d=d, zp=zp, qmin=qmin, qmax=qmax)
    def build(interpret):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
            grid=(M // bm,),
            in_specs=[
                pl.BlockSpec((bm, N), lambda i: (i, 0)),
                pl.BlockSpec((N,), lambda i: (0,)),
                pl.BlockSpec((N,), lambda i: (0,)),
                pl.BlockSpec((N,), lambda i: (0,)),
                pl.BlockSpec((N,), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
            interpret=interpret,
        )

    return pallas_on_platform(build, q, m, s0, lo, hi)
