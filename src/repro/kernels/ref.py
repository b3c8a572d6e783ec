"""Pure-jnp oracles for every Pallas kernel (bit-exact mirrors).

Each oracle implements the *same* integer algorithm as its kernel without
any Pallas machinery, so kernel tests can assert exact integer equality
(tolerance 0).  Where a kernel's algorithm intentionally diverges from
the unfused model path (quant_flash_attention's per-block probability
quantization), that divergence lives HERE, making it auditable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.intmath import unpack_int4

NEG_INF = -1e9


def kv4_unpack_page_ref(blk, rq, kh):
    """Unpack one int4-packed (ps, hd/2) page block back into the int8
    image space with the per-kv-head requant column `rq[:, kh]` (rows
    m, s0, lo, hi, d, zp) — the mirror of the in-kernel unpack in
    `paged_attention._kernel.page_kv` (DESIGN.md §Serving ¶Sub-8-bit
    KV).  Same multiply-shift formula as `core.requant.apply_rqt`."""
    m, s0, lo, hi, d, zp = (rq[i, kh] for i in range(6))
    x = jnp.clip(unpack_int4(blk).astype(jnp.int32), lo, hi)
    staged = jnp.right_shift(x, s0) * m
    out = jnp.right_shift(staged, d - s0) + zp
    return jnp.clip(out, -128, 127).astype(jnp.int8)


def int8_matmul_requant_ref(x, w, bias, mul, s0, *, d: int, zp: int = 0,
                            qmin: int = -128, qmax: int = 127):
    """Mirror of int8_matmul.int8_matmul_requant_pallas."""
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    acc = acc + bias[None, :].astype(jnp.int32)
    staged = jnp.right_shift(acc, s0[None, :]) * mul[None, :]
    out = jnp.right_shift(staged, d - s0[None, :]) + zp
    return jnp.clip(out, qmin, qmax).astype(jnp.int8)


def requant_ref(q, m, s0, lo, hi, *, d: int, zp: int = 0, qmin: int = -128,
                qmax: int = 127):
    """Mirror of requant_kernel.requant_pallas."""
    q = jnp.clip(q, lo[None, :], hi[None, :])
    staged = jnp.right_shift(q, s0[None, :]) * m[None, :]
    out = jnp.right_shift(staged, d - s0[None, :]) + zp
    return jnp.clip(out, qmin, qmax).astype(jnp.int8)


def quant_flash_attention_ref(
    q,
    k,
    v,
    *,
    score_scale: float,
    eps_ctx: float,
    causal: bool = True,
    q_offset: int = 0,
    bq: int = 128,
    bkv: int = 128,
):
    """Mirror of quant_attention: same blockwise online softmax with
    per-block int8 probability images.  q (BH, S_q, hd) int8."""
    BH, S_q, hd = q.shape
    _, S_kv, _ = k.shape
    out = jnp.zeros((BH, S_q, hd), jnp.int8)
    n_q, n_kv = S_q // bq, S_kv // bkv
    q32 = q.astype(jnp.int32)
    k32 = k.astype(jnp.int32)

    def one_qblock(b, i):
        qb = q32[b, i * bq:(i + 1) * bq]
        m_run = jnp.full((bq,), NEG_INF, jnp.float32)
        l_run = jnp.zeros((bq,), jnp.float32)
        acc = jnp.zeros((bq, hd), jnp.float32)
        for j in range(n_kv):
            kb = k32[b, j * bkv:(j + 1) * bkv]
            vb = v[b, j * bkv:(j + 1) * bkv]
            s = qb @ kb.T
            logits = s.astype(jnp.float32) * score_scale
            if causal:
                q_pos = q_offset + i * bq + jnp.arange(bq)[:, None]
                k_pos = j * bkv + jnp.arange(bkv)[None, :]
                logits = jnp.where(k_pos <= q_pos, logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[:, None])
            qp = jnp.round(p * 127.0).astype(jnp.int8)
            pv = jax.lax.dot_general(
                qp, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            corr = jnp.exp(m_run - m_new)
            acc = acc * corr[:, None] + pv.astype(jnp.float32) / 127.0
            l_run = l_run * corr + jnp.sum(qp.astype(jnp.float32), -1) / 127.0
            m_run = m_new
        ctx = acc / jnp.maximum(l_run, 1e-9)[:, None]
        # reciprocal-multiply to match the kernel's f32 rounding exactly
        return jnp.clip(jnp.round(ctx * np.float32(1.0 / eps_ctx)),
                        -128, 127).astype(jnp.int8)

    rows = []
    for b in range(BH):
        blocks = [one_qblock(b, i) for i in range(n_q)]
        rows.append(jnp.concatenate(blocks, axis=0))
    return jnp.stack(rows, axis=0)


def paged_attention_ref(
    q, k_pool, v_pool, table, pos, *, score_scale, group: int = 1,
    k_rq=None, v_rq=None,
):
    """Mirror of paged_attention.paged_attention_pallas: the model's
    unfused multi-query ID attention walked page by page through the
    table — per-page integer score dots staged into one (S, T) logits
    block (query row s causally masked at position pos[b] + s), ONE
    global softmax + int8 probability image per row (eps_p = 1/127),
    per-page integer P.V accumulation.  The float island runs on the
    same-shaped per-row sums as the kernel, so the mirror is bit-exact
    against it (tolerance 0 in tests).

    q (B, H, S, hd) int8; position-minor pools (n_pages + 1, K, hd, ps)
    int8;
    table (B, pps) int32; pos (B,) int32 position of query row 0.
    -> (B, H, S, hd) int32 accumulator (eps_p * eps_v units; ctx_rqt
    applied by the caller).

    With `k_rq`/`v_rq` (6, K) int32 the pools are int4-packed
    (hd/2, ps) and every page read goes through `kv4_unpack_page_ref`
    first — the (S, T) mirror of the packed kernel mode.
    """
    B, H, S, hd = q.shape
    _, K, _, ps = k_pool.shape
    pps = table.shape[1]
    assert H == K * group, (H, K, group)

    def one(b, h):
        qr = q[b, h]                                   # (S, hd) int8
        blocks = []
        for j in range(pps):
            page = table[b, j]
            k_page = k_pool[page, h // group].T        # (ps, hd)
            if k_rq is not None:
                k_page = kv4_unpack_page_ref(k_page, k_rq, h // group)
            s = jax.lax.dot_general(
                qr, k_page, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            lg = s.astype(jnp.float32) * jnp.float32(score_scale)
            q_pos = pos[b] + jnp.arange(S)[:, None]
            k_pos = j * ps + jnp.arange(ps)[None, :]
            blocks.append(lg + jnp.where(k_pos <= q_pos, 0.0, NEG_INF))
        rows = jnp.concatenate(blocks, axis=1)         # (S, T)
        m = jnp.max(rows, axis=-1, keepdims=True)
        p = jnp.exp(rows - m)
        probs = p / jnp.sum(p, axis=-1, keepdims=True)
        qp = jnp.round(probs * 127.0).astype(jnp.int8)
        acc = jnp.zeros((S, hd), jnp.int32)
        for j in range(pps):
            page = table[b, j]
            v_page = v_pool[page, h // group].T
            if v_rq is not None:
                v_page = kv4_unpack_page_ref(v_page, v_rq, h // group)
            acc = acc + jax.lax.dot_general(
                qp[:, j * ps:(j + 1) * ps], v_page,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        return acc

    return jnp.stack(
        [jnp.stack([one(b, h) for h in range(H)]) for b in range(B)]
    )


def paged_attention_decode_ref(
    q, k_pool, v_pool, table, pos, *, score_scale, group: int = 1
):
    """Single-query (S = 1) wrapper of `paged_attention_ref`:
    q (B, H, hd) int8 -> (B, H, hd) int32."""
    out = paged_attention_ref(
        q[:, :, None, :], k_pool, v_pool, table, pos,
        score_scale=score_scale, group=group)
    return out[:, :, 0, :]


def attention_unfused_ref(
    q,
    k,
    v,
    *,
    score_scale: float,
    eps_ctx: float,
    causal: bool = True,
    q_offset=0,
):
    """The model's unfused ID attention (global softmax then one global
    int8 probability image) — used to bound kernel divergence.

    q_offset: scalar, or per-row vector (BH,) mirroring the per-slot
    decode positions of the serving engine (layers/attention._mask).
    """
    BH, S_q, hd = q.shape
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.int32), k.astype(jnp.int32))
    logits = s.astype(jnp.float32) * score_scale
    if causal:
        off = jnp.asarray(q_offset)
        q_pos = off[..., None, None] + jnp.arange(S_q)[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    qp = jnp.round(p * 127.0).astype(jnp.int8)
    acc = jnp.einsum("bqk,bkd->bqd", qp.astype(jnp.int32), v.astype(jnp.int32))
    ctx = acc.astype(jnp.float32) / 127.0
    return jnp.clip(jnp.round(ctx * np.float32(1.0 / eps_ctx)),
                    -128, 127).astype(jnp.int8)
