"""Fused paged-attention integer kernel (W8A8 serving, prefill+decode).

Multi-token (S, T) queries directly over the paged KV arena: the
kernel reads K/V page by page *through the page table*, so the serving
hot path never materializes the dense logical (B, K, T, hd) view that
`layers/attention._paged_kv_view` gathers — that view survives only on
the parity-oracle path (`ServingConfig(paged_kernel=False)`).  S = 1 is
single-token decode; S = C is a chunked-prefill block; the serving
engine issues ONE unified dispatch where decode rows and prefill-chunk
rows share the same (B, H, S, hd) query batch (DESIGN.md §Serving
¶Unified attention kernel).

Algorithm — the model's unfused ID attention, bit for bit:

    per page j (physical id table[b, j]):
      s_j      = q_i8 . k_page_i8^T            int32, MXU int8 path
      logits_j = s_j * score_scale + mask      staged into VMEM rows
    == float island (one (S, T) block in VMEM) ==
      probs    = softmax(logits)               max / exp / sum / divide
      qp       = round(127 * probs)            int8 image, eps_p = 1/127
    == island exit ==
      per page j:  acc += qp_j . v_page_i8     int32 accumulator
    out_i32 = acc                              (ctx_rqt applied outside)

The full (S, T) probability block fits in VMEM scratch (S is a small
chunk width), so the kernel can afford the model's *global*
probability image instead of flash-attention's per-block online
re-quantization (`kernels/quant_attention.py`) — no per-page requant,
ever.  That choice is what makes the kernel BIT-EXACT with the
write-then-gather jnp path — and therefore with the contiguous
SlotArena path — rather than approximately close: every cross-element
reduction is an integer dot, an order-free max, or the same
per-row (., T) float sum XLA emits for the unfused softmax (per-page
partial sums would NOT reproduce it; the logits rows are staged so
one full-row sum runs per query row).  Engine tests pin
kernel == gather == SlotArena token-for-token on that basis in the
interpreter; on a TPU the island's exp, sum and divide are Mosaic's,
and `chip_smoke.py` compares its tokens with XLA's gather path.

Memory scope (DESIGN.md §Serving ¶Paged-attention kernel memory
scope): the page table, the positions, the score scale and the int4
requant operands live in SMEM (table and positions as scalar
prefetch); the K/V pools stay in HBM (`memory_space=ANY`) and every
page is copied into a double-buffered (2, hd, ps) VMEM buffer by an
async DMA — page j + 1 is in flight while page j is used — so the
arena's size is bounded by HBM, not VMEM.  Pools are position-minor,
(n_pages + 1, K, hd, ps): a (ps, hd = 64) page would be padded to 128
lanes in HBM and Mosaic refuses to copy it, while a (hd, ps) page is
dense in HBM and copies whole.  The probability image is
staged in a VMEM scratch ref and read back page by page.  Every
dynamic lane offset is j * ps, so on a TPU the page size must be a
multiple of `TPU_PAGE_ALIGN` (128 lanes); the serving engine refuses
any other page size on a TPU.

Masking contract (serving.cache.PagedArena layout):

  * query row s sits at logical position `pos[b] + s` (pos is the
    position of the FIRST query row; for decode S = 1 it is the
    familiar per-slot decode position).  Key positions past that take
    the same -1e9 additive causal mask as `layers/attention._mask` —
    stale pages of a recycled slot, the padded tail of the last
    partial page, and the not-yet-written suffix of a mid-prefill
    chunk surface nothing;
  * PAGE_NULL table entries point at physical page 0 (the trash page)
    and only ever cover fully-masked logical blocks of live rows;
  * rows parked at INACTIVE_POS keep every position (their tables are
    all PAGE_NULL, so they attend over deterministic trash) — garbage
    in, garbage out, exactly like the gather path: the engine never
    reads logits of inactive rows.

GQA is folded into the page loads (kv head = h // group) — no
head-expanded K/V copy exists anywhere.  `score_scale` may be a traced
scalar (layer-stacked tables under lax.scan).

Int4-packed pools (DESIGN.md §Serving ¶Sub-8-bit KV) store the low and
high nibbles of a cell for head-dim rows 2i and 2i + 1.  The kernel
never interleaves them back: it splits q into its even and odd columns
outside the kernel, dots each half against the matching nibble plane
(integer sums, so the score is exact), and returns the P.V accumulator
as two planes that the wrapper interleaves again.

`kernels/ref.py::paged_attention_ref` is the pure-jnp mirror of
exactly this algorithm; tests pin kernel == mirror at tolerance 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_on_platform

NEG_INF = -1e9
# lane width of a TPU vector register: the logits and probability
# rows are addressed at lane offset j * page_size
TPU_PAGE_ALIGN = 128


def _unpack_planes(blk, rq_ref, kh):
    """An int4-packed (hd/2, ps) page -> (low, high) nibble planes,
    each requantized into the int8 image space with the kv head's
    column of `rq_ref` (rows m, s0, lo, hi, d, zp) — the formula of
    `core.requant.apply_rqt`, on int32 lanes."""
    m, s0, lo, hi, d, zp = (rq_ref[i, kh] for i in range(6))
    x = blk.astype(jnp.int32)
    planes = []
    for nib in (jnp.right_shift(jnp.left_shift(x, 28), 28),
                jnp.right_shift(x, 4)):
        staged = jnp.right_shift(jnp.clip(nib, lo, hi), s0) * m
        out = jnp.right_shift(staged, d - s0) + zp
        planes.append(jnp.clip(out, -128, 127).astype(jnp.int8))
    return planes


def _kernel(
    table_ref,
    pos_ref,
    q_ref,
    k_hbm,
    v_hbm,
    scale_ref,
    *rest,
    ps: int,
    pps: int,
    group: int,
    s_q: int,
    packed: bool,
    wide_dots: bool,
):
    """One (slot b, head h) grid step.  q_ref/o_ref carry the head dim
    as (n_planes, S, hd / n_planes): one plane for int8 pools, the
    even and odd columns for int4-packed pools.

    `wide_dots` (the interpreter's build) widens the int8 dot operands
    to int32 — the same integer products and sums — because XLA's CPU
    backend emits invalid code for an int8 dot accumulated in a loop."""
    if packed:
        k_rq, v_rq, o_ref, logits_ref, qp_ref, buf, sem = rest
    else:
        o_ref, logits_ref, qp_ref, buf, sem = rest
        k_rq = v_rq = None
    b = pl.program_id(0)
    kh = pl.program_id(1) // group
    pos_b = pos_ref[b]
    scale = scale_ref[0, 0]
    n_planes = q_ref.shape[2]
    q = [q_ref[0, 0, i] for i in range(n_planes)]  # (S, hd_p) int8 each

    def copy(pool, j, slot):
        return pltpu.make_async_copy(
            pool.at[table_ref[b, j], kh], buf.at[slot], sem.at[slot]
        )

    def page_loop(pool, rq_ref, body, carry):
        """body(j, planes, carry) over the table row's pages, page j + 1
        copied while page j is used."""
        copy(pool, 0, 0).start()

        def step(j, carry):
            slot = j % 2

            @pl.when(j + 1 < pps)
            def _():
                copy(pool, j + 1, 1 - slot).start()

            copy(pool, j, slot).wait()
            blk = buf[slot]  # (hd_store, ps) int8
            planes = _unpack_planes(blk, rq_ref, kh) if packed else [blk]
            return body(j, planes, carry)

        return jax.lax.fori_loop(0, pps, step, carry)

    def lanes(j):
        return pl.ds(pl.multiple_of(j * ps, ps), ps)

    def dot(a, b, b_axis):
        """a (M, n) . b contracted over its axis `b_axis` -> int32."""
        if wide_dots:
            a, b = a.astype(jnp.int32), b.astype(jnp.int32)
        return jax.lax.dot_general(
            a, b, (((1,), (b_axis,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    def score(j, k_planes, carry):
        s = dot(q[0], k_planes[0], 0)
        for qi, ki in zip(q[1:], k_planes[1:]):
            s = s + dot(qi, ki, 0)  # (S, ps)
        lg = s.astype(jnp.float32) * scale
        # query row s sits at position pos_b + s; causal mask per row
        q_pos = pos_b + jax.lax.broadcasted_iota(jnp.int32, (s_q, ps), 0)
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (s_q, ps), 1)
        logits_ref[:, lanes(j)] = lg + jnp.where(k_pos <= q_pos, 0.0, NEG_INF)
        return carry

    page_loop(k_hbm, k_rq, score, 0)

    # ---- float island: the model's global probability image ----
    rows = logits_ref[...]  # (S, T)
    m = jnp.max(rows, axis=-1, keepdims=True)
    p = jnp.exp(rows - m)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    qp_ref[...] = jnp.round(probs * 127.0).astype(jnp.int8)  # island exit
    # ---- island exit: integer P.V over pages ----

    def pv(j, v_planes, acc):
        qp_j = qp_ref[:, lanes(j)]  # (S, ps) int8
        return tuple(a + dot(qp_j, vi, 1) for a, vi in zip(acc, v_planes))

    acc0 = tuple(jnp.zeros((s_q, qi.shape[-1]), jnp.int32) for qi in q)
    acc = page_loop(v_hbm, v_rq, pv, acc0)
    for i, a in enumerate(acc):
        o_ref[0, 0, i] = a


def _planes(x, n_planes):
    """(B, H, S, hd) -> (B, H, n_planes, S, hd / n_planes): column
    c goes to plane c % n_planes."""
    B, H, S, hd = x.shape
    x = x.reshape(B, H, S, hd // n_planes, n_planes)
    return jnp.moveaxis(x, -1, 2)


def _unplanes(x):
    """Inverse of `_planes`."""
    B, H, n_planes, S, hd_p = x.shape
    return jnp.moveaxis(x, 2, -1).reshape(B, H, S, hd_p * n_planes)


def paged_attention_pallas(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    score_scale,
    group: int = 1,
    k_rq=None,
    v_rq=None,
):
    """q (B, H, S, hd) int8 — S query rows per slot, row s at logical
    position pos[b] + s; k/v pools (n_pages + 1, K, hd, ps) int8;
    table (B, pps) int32 physical page ids; pos (B,) int32 position of
    the FIRST query row (INACTIVE_POS for parked rows).
    -> (B, H, S, hd) int32 P.V accumulator in eps_p * eps_v units (the
    caller owns the `ctx_rqt` requantization, like every Linear in
    this codebase).

    Int4-packed pools (DESIGN.md §Serving ¶Sub-8-bit KV) have a
    (hd/2, ps) trailing block; pass the per-kv-head unpack images as
    `k_rq`/`v_rq` (6, K) int32 operands and the kernel unpacks inside
    the page loop.
    """
    B, H, S, hd = q.shape
    n_pool, K, hd_store, ps = k_pool.shape
    pps = table.shape[1]
    assert H == K * group, (H, K, group)
    packed = hd_store != hd
    if packed:
        if 2 * hd_store != hd or k_rq is None or v_rq is None:
            raise ValueError(
                f"pool head_dim {hd_store} != query head_dim {hd}: "
                "int4-packed pools need hd/2 cells plus k_rq/v_rq "
                "(6, K) requant operands"
            )
    elif k_rq is not None or v_rq is not None:
        raise ValueError("k_rq/v_rq given but the pools are not packed")
    n_planes = 2 if packed else 1
    hd_p = hd // n_planes
    scale = jnp.asarray(score_scale, jnp.float32).reshape(1, 1)
    qo_block = (1, 1, n_planes, S, hd_p)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec(qo_block, lambda b, h, *_: (b, h, 0, 0, 0)),
        hbm,
        hbm,
        smem,
    ]
    operands = [_planes(q, n_planes), k_pool, v_pool, scale]
    if packed:
        in_specs += [smem, smem]
        operands += [
            jnp.asarray(k_rq, jnp.int32), jnp.asarray(v_rq, jnp.int32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(qo_block, lambda b, h, *_: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((S, pps * ps), jnp.float32),  # logits rows
            pltpu.VMEM((S, pps * ps), jnp.int8),  # probability image
            pltpu.VMEM((2, hd_store, ps), jnp.int8),  # page buffers
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    def build(interpret):
        kern = functools.partial(
            _kernel, ps=ps, pps=pps, group=group, s_q=S, packed=packed,
            wide_dots=interpret,
        )
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(
                (B, H, n_planes, S, hd_p), jnp.int32
            ),
            grid_spec=grid_spec,
            interpret=interpret,
            name="paged_attention",
        )

    out = pallas_on_platform(
        build, table.astype(jnp.int32), pos.astype(jnp.int32), *operands
    )
    return _unplanes(out)


def paged_attention(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    score_scale,
    group: int = 1,
    mesh=None,
    k_rq=None,
    v_rq=None,
):
    """Mesh-aware dispatch for the fused paged attention (same contract
    as `paged_attention_pallas`, plus an optional serving mesh).

    With a mesh whose "model" axis divides the kv-head count, the
    kernel runs under shard_map with a per-shard head range: the pools
    arrive split along their K axis, q along H, and each shard executes
    the unmodified kernel over its own K/n kv heads and the matching
    H/n query heads.  GQA groups never straddle a shard boundary —
    H = K * group is sharded in the same contiguous blocks as K, so the
    local `h // group` fold still lands on the local kv head — and the
    per-head math is untouched, so the sharded call is bit-exact with
    the single-shard one (each (b, h) grid cell computes on exactly the
    same bytes, just on a different device).  The page table, position
    vector, and score_scale are replicated: every shard walks the full
    table (pages hold all kv heads; only the head axis splits).

    Falls back to the plain call when there is no mesh, the model axis
    is width 1, or it does not divide K (the GQA-aware replication
    fallback of sharding/rules.arena_leaf_spec — the pools are then
    replicated too, and the constraint-free call matches them).
    """
    n = dict(mesh.shape).get("model", 1) if mesh is not None else 1
    K = k_pool.shape[1]
    if n <= 1 or K % n:
        return paged_attention_pallas(
            q, k_pool, v_pool, table, pos,
            score_scale=score_scale, group=group,
            k_rq=k_rq, v_rq=v_rq,
        )
    from jax.sharding import PartitionSpec as P

    packed = k_rq is not None

    def local(q_, k_, v_, tab_, pos_, scale_, *rq_):
        kr, vr = rq_ if packed else (None, None)
        return paged_attention_pallas(
            q_, k_, v_, tab_, pos_,
            score_scale=scale_, group=group,
            k_rq=kr, v_rq=vr,
        )

    in_specs = [
        P(None, "model", None, None),
        P(None, "model", None, None),
        P(None, "model", None, None),
        P(),
        P(),
        P(),
    ]
    operands = [
        q, k_pool, v_pool, table.astype(jnp.int32), pos.astype(jnp.int32),
        jnp.asarray(score_scale, jnp.float32),
    ]
    if packed:
        # the (6, K) requant operands split with the kv heads — each
        # shard gets the columns of its own head range
        in_specs += [P(None, "model"), P(None, "model")]
        operands += [
            jnp.asarray(k_rq, jnp.int32), jnp.asarray(v_rq, jnp.int32),
        ]

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, "model", None, None),
        check_vma=False,
    )
    return sharded(*operands)


def paged_attention_decode_pallas(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    score_scale,
    group: int = 1,
    k_rq=None,
    v_rq=None,
):
    """Single-token wrapper: q (B, H, hd) int8 -> (B, H, hd) int32.
    The S = 1 case of `paged_attention_pallas` (pos is the decode
    position of the one query row)."""
    out = paged_attention_pallas(
        q[:, :, None, :], k_pool, v_pool, table, pos,
        score_scale=score_scale, group=group,
        k_rq=k_rq, v_rq=v_rq,
    )
    return out[:, :, 0, :]


def paged_attention_decode(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    score_scale,
    group: int = 1,
    mesh=None,
    k_rq=None,
    v_rq=None,
):
    """Single-token wrapper over the mesh-aware `paged_attention`:
    q (B, H, hd) int8 -> (B, H, hd) int32."""
    out = paged_attention(
        q[:, :, None, :], k_pool, v_pool, table, pos,
        score_scale=score_scale, group=group, mesh=mesh,
        k_rq=k_rq, v_rq=v_rq,
    )
    return out[:, :, 0, :]
