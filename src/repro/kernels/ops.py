"""Jitted public wrappers around the Pallas kernels.

Handle ragged shapes (pad to block multiples, slice back), GQA head
grouping, and table plumbing from `RequantParams`/rqt trees.  These are
the entry points the serving path uses when `use_kernels=True`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.int8_matmul import int8_matmul_requant_pallas
from repro.kernels.quant_attention import quant_flash_attention_pallas
from repro.kernels.requant_kernel import requant_pallas


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


@functools.partial(
    jax.jit,
    static_argnames=("d", "zp", "qmin", "qmax", "bm", "bn", "bk"),
)
def int8_matmul_requant(x, w, bias, mul, s0, *, d: int, zp: int = 0,
                        qmin: int = -128, qmax: int = 127, bm: int = 128,
                        bn: int = 128, bk: int = 128):
    """x (..., K) int8 @ w (K, N) int8 -> (..., N) int8, requantized.

    Arbitrary leading dims; K/N padded to block multiples internally.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    x2, M0 = _pad_to(x2, bm, 0)
    x2, _ = _pad_to(x2, bk, 1)
    w2, _ = _pad_to(w, bk, 0)
    w2, _ = _pad_to(w2, bn, 1)
    pad_n = w2.shape[1]

    def padv(v, fill=0):
        return jnp.pad(v, (0, pad_n - N), constant_values=fill)

    out = int8_matmul_requant_pallas(
        x2, w2, padv(bias), padv(mul, 1), padv(s0), d=d, zp=zp,
        qmin=qmin, qmax=qmax, bm=bm, bn=bn, bk=bk)
    return out[:M0, :N].reshape(*lead, N)


def linear_rqt_kernel(s_x, ip: dict, rqt: dict):
    """Model-facing fusion: QLinear.apply_id + apply_rqt in one kernel.

    ip: {"w_q", "b_q"}; rqt: {"m","d","s0","lo","hi","zp"} (d scalar).
    The rqt pre-clip (lo/hi) is subsumed by the int8 output clip for
    linear sites (downscale, zp'd clip) — verified against apply_rqt in
    tests.
    """
    d = int(np.asarray(rqt["d"]))
    zp = int(np.asarray(rqt["zp"]))
    N = ip["w_q"].shape[-1]
    mul = jnp.broadcast_to(jnp.asarray(rqt["m"], jnp.int32), (N,))
    s0 = jnp.broadcast_to(jnp.asarray(rqt["s0"], jnp.int32), (N,))
    return int8_matmul_requant(
        s_x, ip["w_q"], ip["b_q"], mul, s0, d=d, zp=zp)


@functools.partial(
    jax.jit, static_argnames=("d", "zp", "qmin", "qmax", "bm")
)
def requant(q, m, s0, lo, hi, *, d: int, zp: int = 0, qmin: int = -128,
            qmax: int = 127, bm: int = 256):
    """q (..., N) int32 -> (..., N) int8 via the VPU kernel."""
    lead = q.shape[:-1]
    N = q.shape[-1]
    q2 = q.reshape(-1, N)
    q2, M0 = _pad_to(q2, bm, 0)
    out = requant_pallas(
        q2,
        m,
        s0,
        lo,
        hi,
        d=d,
        zp=zp,
        qmin=qmin,
        qmax=qmax,
        bm=bm,
    )
    return out[:M0].reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=(
    "score_scale", "eps_ctx", "causal", "q_offset", "bq", "bkv",
    "n_rep"))
def quant_flash_attention(
    q,
    k,
    v,
    *,
    score_scale: float,
    eps_ctx: float,
    causal: bool = True,
    q_offset: int = 0,
    n_rep: int = 1,
    bq: int = 128,
    bkv: int = 128,
):
    """GQA wrapper.  q (B, H, S_q, hd); k/v (B, K, S_kv, hd) int8;
    n_rep = H // K.  Returns (B, H, S_q, hd) int8 ctx image."""
    B, H, S_q, hd = q.shape
    _, Kh, S_kv, _ = k.shape
    assert H == Kh * n_rep
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
    qf = q.reshape(B * H, S_q, hd)
    kf = k.reshape(B * H, S_kv, hd)
    vf = v.reshape(B * H, S_kv, hd)
    qf, Sq0 = _pad_to(qf, bq, 1)
    out = quant_flash_attention_pallas(
        qf, kf, vf, score_scale=score_scale, eps_ctx=eps_ctx,
        causal=causal, q_offset=q_offset, bq=bq, bkv=bkv)
    return out[:, :Sq0].reshape(B, H, S_q, hd)
