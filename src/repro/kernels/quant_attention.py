"""Quantized flash attention Pallas kernel (W8A8 serving, DESIGN.md §6).

Streaming over KV blocks with online softmax; the §3.8 float island is
confined to VMEM registers (running max / normalizer):

    per KV block j:
      s_j    = q_i8 . k_j_i8^T                  int32, MXU int8 path
      l_j    = s_j * score_scale + mask         f32 island
      m_new  = max(m_old, rowmax(l_j))
      p_j    = exp(l_j - m_new)                 in (0, 1]
      qp_j   = round(127 * p_j)                 int8 image, eps_p = 1/127
      acc    = acc * e^(m_old - m_new) + (qp_j . v_j_i8)/127    (PV on MXU)
      l_sum  = l_sum * e^(m_old - m_new) + sum(qp_j)/127
    out_i8  = clip(round( (acc / l_sum) * inv_eps_ctx ))

The P block is re-quantized *per block* against the running max — this is
the kernel's defining approximation vs. the unfused jnp path (which
quantizes probabilities after the full softmax).  ref.py carries a
pure-jnp mirror of exactly this blockwise algorithm (the oracle), and a
second test bounds kernel-vs-unfused divergence in ctx quanta.

Grid: (B*H, S_q/bq) with a fori_loop over KV blocks inside the kernel
(sequential dimension), carrying (m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_on_platform

NEG_INF = -1e9


def _kernel(q_ref, k_ref, v_ref, o_ref, *, score_scale: float,
            inv_eps_ctx: float, bkv: int, kv_len: int, q_offset: int,
            causal: bool, bq: int):
    """q (bq, hd) int8; k/v (kv_len, hd) int8; o (bq, hd) int8."""
    i = pl.program_id(1)  # query block index
    hd = q_ref.shape[-1]
    q = q_ref[0]            # block specs carry a leading (1,) batch dim
    n_kv = kv_len // bkv

    def body(j, carry):
        m_old, l_old, acc = carry
        k_blk = k_ref[0, pl.ds(j * bkv, bkv), :]
        v_blk = v_ref[0, pl.ds(j * bkv, bkv), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)          # (bq, bkv)
        logits = s.astype(jnp.float32) * score_scale
        if causal:
            q_pos = q_offset + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 0)
            k_pos = j * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            logits = jnp.where(k_pos <= q_pos, logits, NEG_INF)
        m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        qp = jnp.round(p * 127.0).astype(jnp.int8)     # island exit
        pv = jax.lax.dot_general(
            qp, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)          # (bq, hd)
        corr = jnp.exp(m_old - m_new)
        acc = acc * corr[:, None] + pv.astype(jnp.float32) * (1.0 / 127.0)
        l_new = l_old * corr + jnp.sum(
            qp.astype(jnp.float32), axis=-1) * (1.0 / 127.0)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m_f, l_f, acc_f = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    ctx = acc_f / jnp.maximum(l_f, 1e-9)[:, None]
    o_ref[0] = jnp.clip(jnp.round(ctx * inv_eps_ctx), -128, 127
                        ).astype(jnp.int8)


def quant_flash_attention_pallas(
    q, k, v, *, score_scale: float, eps_ctx: float, causal: bool = True,
    q_offset: int = 0, bq: int = 128, bkv: int = 128,
):
    """q (BH, S_q, hd) int8; k/v (BH, S_kv, hd) int8 -> (BH, S_q, hd) int8.

    GQA callers expand/regroup heads before the call (ops.py).  S_q must
    divide by bq and S_kv by bkv.
    """
    BH, S_q, hd = q.shape
    _, S_kv, _ = k.shape
    assert S_q % bq == 0 and S_kv % bkv == 0, (S_q, S_kv, bq, bkv)
    kern = functools.partial(
        _kernel, score_scale=float(score_scale),
        inv_eps_ctx=float(1.0 / eps_ctx), bkv=bkv, kv_len=S_kv,
        q_offset=q_offset, causal=causal, bq=bq)
    def build(interpret):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((BH, S_q, hd), jnp.int8),
            grid=(BH, S_q // bq),
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, S_kv, hd), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, S_kv, hd), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            interpret=interpret,
        )

    return pallas_on_platform(build, q, k, v)
