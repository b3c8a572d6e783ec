"""Grouped-query attention with an integer-only serving path.

ID dataflow (DESIGN.md §3.7 island (a)):

    s_x  --wq/wk/wv (int8 dot)-->  int32 acc
         --requant (QAct sym)-->   int8 q,k,v images          (zp=0)
         --integer RoPE-->         int8 q,k                   (eps preserved)
         --int8 QK^T-->            int32 scores
         == float island ==        scores * (eps_q*eps_k/sqrt(hd)) + mask
                                   softmax -> probs in [0,1]
                                   round(probs * 127) -> int8
                                   (zp=0, eps=1/127)
         == island exit ==
         --int8 P.V-->             int32 acc  (bounded: sum p_img ~ 127)
         --requant-->              int8 attention output
         --wo (int8 dot)-->        int32 acc  (consumed by the block's Add)

The probs space deliberately spends the sign bit (eps_p = 1/127, zp=0) so
the P.V accumulator needs no dynamic zero-point correction — the paper's
offset-correction economics (Eq. 15) applied to attention.

KV cache: int8 images + static eps in ID; model dtype in FP/FQ.  Decode
(`pos is not None`) updates the cache at one position and masks by index.

Continuous batching (repro.serving): `pos` may be a per-slot vector
(B,) instead of a scalar — every batch row then decodes at its *own*
sequence offset (ragged positions).  RoPE gather, causal masking, and
the one-hot cache write all broadcast the per-row position; the math at
each row is identical to the scalar-pos path at that row's offset.

Chunked prefill (repro.serving, batched): a per-slot `pos` vector with
S > 1 writes an S-token *chunk* into each row's cache at that row's own
offset — the packed prefill dispatch of ServingEngine, where row b
carries tokens [pos[b], pos[b] + S) of its prompt.  Rows parked at
INACTIVE_POS (free or decoding slots riding along in the fixed-shape
dispatch, and the padded tail of a final partial chunk past the arena)
write nothing: the per-row write helpers mask every target position
>= the cache length, so a packed prefill can never corrupt a
neighboring slot's cache.  Their attention math still runs (garbage in,
garbage out) but the engine reads logits only from rows whose final
chunk completed.

Sliding window (`window` W > 0, per layer kind — configs.base.AttnKind):
the query at position i sees keys j with i - W < j <= i, in every path
(the masks below, and the paged kernel's page walk and mask); YaRN
rotary (`yarn`) scales q.k by its attention factor squared on the
score scale, so the rope tables stay within [-1, 1].

Paged KV (serving.cache.PagedArena): a decode cache dict may carry a
per-slot page "table" (B, pages_per_slot) next to its pooled "k"/"v"
leaves (n_pages + 1, K, hd, page_size) — position-minor, the layout
the TPU kernel copies page by page (kernels/paged_attention.py).  The
new column(s) are scattered into the pages holding each row's `pos`;
ID attention — single-token decode AND multi-token chunked-prefill —
then runs the
fused paged-attention kernel straight over the pools
(kernels/paged_attention.py — bit-exact with the unfused math, see
its module doc) unless `variants paged_decode="gather"` selects the
oracle path, which gathers the logical (B, K, T, hd) view back
through the table.  Positions past each query row's position (stale
pages, the PAGE_NULL trash page, the unwritten suffix of a chunk)
are hidden by the same per-slot causal masking either way, so the
paged path is bit-exact with the contiguous one.

Multi-device serving (DESIGN.md §Serving ¶Multi-device): under a mesh
profile the serving engine shards the cache arena along kv heads on
the "model" axis.  The per-slot write helpers below are elementwise
along the head axis, so they partition without collectives; the
"kv_heads" constraints pin that layout through write and gather, and
the fused paged kernel runs with a per-shard head range (shard_map in
kernels/paged_attention.py).  Integer accumulation is exactly
associative and the float softmax island is per-(row, head), so the
sharded math is BIT-EXACT with single-device serving — parity is
pinned token-for-token in tests/test_serving_sharded.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import Yarn
from repro.core.intmath import pack_int4, unpack_int4
from repro.core.requant import apply_rqt, make_rqt
from repro.core.rep import Rep
from repro.layers.act_quant import QAct
from repro.layers.common import ActKind, DeployCtx
from repro.layers.linear import QLinear
from repro.layers.rope import (
    apply_rope_fp, apply_rope_int, rope_tables_fp, rope_tables_int,
)

EPS_P = 1.0 / 127.0  # probability quantum (symmetric int8, zp=0)
NEG_INF = -1e9
PAGE_NULL = 0  # physical page 0 is the trash page (serving.cache re-exports)
# Rows of a packed (decode or chunked-prefill) dispatch that carry no
# real work are parked at this position: far past any cache length, so
# every per-row cache write masks to a no-op, yet small enough that
# pos + chunk stays int32-safe.
INACTIVE_POS = 1 << 30


@dataclasses.dataclass(frozen=True)
class QAttention:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_base: float = 10000.0
    rope_fraction: float = 1.0
    max_seq: int = 4096
    name: str = "attn"
    d_in: int = 0  # input width if != d_model (zamba2 shared block concat)
    # sliding window: 0 is full causal; W > 0 lets the query at position
    # i see keys j with i - W < j <= i (configs.base.AttnKind)
    window: int = 0
    yarn: Optional[Yarn] = None  # YaRN rotary scaling (layers/rope.py)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    def _rope_tables(self, make):
        return make(self.head_dim, self.max_seq, self.rope_base,
                    self.rope_fraction, self.yarn)

    def _yarn_gain(self, scores):
        """YaRN's attention factor scales q and k alike, so q.k by its
        square (HF scales cos and sin)."""
        if self.yarn is None:
            return scores
        return scores * np.float32(self.yarn.attention_factor ** 2)

    def _sub(self):
        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        d_in = self.d_in or self.d_model
        return {
            "wq": QLinear(d_in, H * hd),
            "wk": QLinear(d_in, K * hd),
            "wv": QLinear(d_in, K * hd),
            "wo": QLinear(H * hd, self.d_model),
        }

    def init(self, key) -> dict:
        subs = self._sub()
        keys = jax.random.split(key, len(subs))
        return {n: lay.init(k) for (n, lay), k in zip(subs.items(), keys)}

    # ------------------------------------------------------------------
    def _shape_qkv(self, q, k, v, B, S):
        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)   # (B,H,S,hd)
        k = k.reshape(B, S, K, hd).transpose(0, 2, 1, 3)   # (B,K,S,hd)
        v = v.reshape(B, S, K, hd).transpose(0, 2, 1, 3)
        return q, k, v

    def _expand_kv(self, k):
        """(B, K, S, hd) -> (B, H, S, hd): repeat so the head axis keeps
        full H divisibility for model-axis sharding (the K,G grouped
        layout would leave probs unshardable whenever K < mesh model)."""
        if self.group == 1:
            return k
        return jnp.repeat(k, self.group, axis=1)

    # -- float path ------------------------------------------------------
    def apply_float(self, p, x, rep, *, cache=None, pos=None,
                    calib=None, scope: str = ""):
        """FP/FQ/QD forward.  x: (B, S, d) float.  Returns (y, cache)."""
        from repro.sharding.hints import hint

        subs = self._sub()
        B, S, _ = x.shape
        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        q = subs["wq"].apply(p["wq"], x, rep)
        k = subs["wk"].apply(p["wk"], x, rep)
        v = subs["wv"].apply(p["wv"], x, rep)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.q", q)
            calib.observe(f"{scope}{self.name}.k", k)
            calib.observe(f"{scope}{self.name}.v", v)
        q, k, v = self._shape_qkv(q, k, v, B, S)
        if S > 1:  # decode: q stays unhinted so GSPMD follows the
            q = hint(q, "act_bhsd")  # sequence-sharded cache layout
        rot, cos, sin = self._rope_tables(rope_tables_fp)
        positions = _positions(S, pos)
        q = apply_rope_fp(q, cos, sin, positions, rot)
        k = apply_rope_fp(k, cos, sin, positions, rot)
        if calib is not None:
            # per-kv-head ranges for the int4-packed KV images
            # (DESIGN.md §Serving ¶Sub-8-bit KV) — observed POST-RoPE,
            # exactly what the KV cache stores, so the int4 grids need
            # no rotation headroom
            for h in range(K):
                calib.observe(f"{scope}{self.name}.k.h{h}", k[:, h])
                calib.observe(f"{scope}{self.name}.v.h{h}", v[:, h])

        if cache is not None:
            if "table" in cache:
                k_all, v_all, cache = _paged_cache_update(cache, k, v, pos)
            else:
                k_all = _cache_write(
                    cache["k"], k.astype(cache["k"].dtype), pos
                )
                v_all = _cache_write(
                    cache["v"], v.astype(cache["v"].dtype), pos
                )
                cache = {"k": k_all, "v": v_all}
            k, v = k_all.astype(x.dtype), v_all.astype(x.dtype)
        T = k.shape[2]

        # decode (S==1): keep the cache's sequence sharding — hinting to
        # head-sharded would all-gather the whole KV cache every token
        kh = self._expand_kv(k) if S == 1 else hint(
            self._expand_kv(k), "act_bhsd")
        vh = self._expand_kv(v) if S == 1 else hint(
            self._expand_kv(v), "act_bhsd")
        scores = jnp.einsum("bhsd,bhtd->bhst", q, kh,
                            preferred_element_type=jnp.float32)
        scores = self._yarn_gain(scores / np.sqrt(hd))
        scores = scores + _mask(S, T, pos, self.window)
        probs = hint(
            jax.nn.softmax(scores, axis=-1), "probs_dec" if S == 1 else "probs"
        )
        if calib is not None:
            calib.observe(f"{scope}{self.name}.probs", probs)
        ctx_ = jnp.einsum("bhst,bhtd->bhsd", probs.astype(x.dtype), vh)
        ctx_ = ctx_.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.ctx", ctx_)
        y = subs["wo"].apply(p["wo"], ctx_, rep)
        return y, cache

    # -- calibration helpers ----------------------------------------------
    def _qkv_acts(self):
        rt2 = float(np.sqrt(2.0))  # RoPE rotation headroom
        return {
            "q": QAct(
                ActKind.IDENTITY, sym=True, range_scale=rt2,
                name=f"{self.name}.q",
            ),
            "k": QAct(
                ActKind.IDENTITY, sym=True, range_scale=rt2,
                name=f"{self.name}.k",
            ),
            "v": QAct(ActKind.IDENTITY, sym=True, name=f"{self.name}.v"),
            "ctx": QAct(ActKind.IDENTITY, sym=True, name=f"{self.name}.ctx"),
        }

    # -- transform ---------------------------------------------------------
    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_x: float, zp_x: int
    ) -> Tuple[dict, np.ndarray]:
        """-> (tables, eps_acc_out per-channel of wo accumulator)."""
        subs = self._sub()
        acts = self._qkv_acts()
        t: dict = {}
        eps = {}
        for nm in ("wq", "wk", "wv"):
            ip, eps_acc = subs[nm].deploy(p_np[nm], eps_x, zp_x)
            t[nm] = ip
            short = nm[1]
            a_t, a_eps, a_zp = acts[short].deploy(
                ctx, scope, eps_acc, 0, subs[nm].acc_bound())
            assert a_zp == 0
            t[f"{short}_rqt"] = a_t["rqt"]
            eps[short] = a_eps
        # island scale: int32 scores * eps_q*eps_k/sqrt(hd) -> f32 logits
        eps_s = eps["q"] * eps["k"] / np.sqrt(self.head_dim)
        if self.yarn is not None:
            eps_s = eps_s * self.yarn.attention_factor ** 2
        t["score_scale"] = np.float32(eps_s)
        # integer-softmax tables (attn_softmax=int variant; all-int32)
        from repro.core.intsoftmax import make_int_softmax_tables

        t["sm_tabs"] = make_int_softmax_tables(float(eps_s))
        # P.V accumulator -> int8 ctx image
        ctx_t, ctx_eps, ctx_zp = acts["ctx"].deploy(
            ctx, scope, EPS_P * eps["v"], 0,
            acc_bound=260.0 * 127.0,  # sum p_img <~ 127 + S/2 quanta slack
        )
        assert ctx_zp == 0
        t["ctx_rqt"] = ctx_t["rqt"]
        # sub-8-bit KV (DESIGN.md §Serving ¶Sub-8-bit KV): per-kv-head
        # pack/unpack requant images between the int8 KV image space
        # and the int4 page-pool space; unused unless the serving
        # arena is packed (kv_bits=4)
        t["kv4"] = self._kv4_tables(ctx, scope, eps)
        ip, eps_acc_o = subs["wo"].deploy(p_np["wo"], ctx_eps, 0)
        t["wo"] = ip
        return t, eps_acc_o

    def _kv4_tables(self, ctx: DeployCtx, scope: str, eps: dict) -> dict:
        """Per-kv-head int4 requant images for the packed KV arena.

        Calibrated the same way activations are: the per-head float
        ranges observed by `apply_float` (names ``{k,v}.h{h}``, taken
        POST-RoPE — exactly what the cache stores, so no rotation
        headroom) set each head's int4 quantum ``eps4_h`` in
        int8-IMAGE units — abs-max/7, floored at 1 so int4 never
        claims precision the int8 image lacks.  ``*_pack`` maps the
        int8 image into [-8, 7] (ratio 1/eps4); ``*_unpack`` maps
        stored int4 back into the SAME int8 image space (ratio eps4)
        — score_scale, the softmax island, and ctx_rqt are untouched
        downstream.  Heads missing from calibration fall back to the
        full image range."""
        out = {}
        for short in ("k", "v"):
            eps8 = float(eps[short])
            amax_img = np.empty(self.n_kv_heads, np.float64)
            for h in range(self.n_kv_heads):
                nm = f"{scope}{self.name}.{short}.h{h}"
                if ctx.calib is not None and nm in getattr(
                    ctx.calib, "hi", {}
                ):
                    lo, hi = ctx.calib.range(nm)
                    amax_img[h] = (
                        max(abs(float(lo)), abs(float(hi))) / eps8
                    )
                else:
                    amax_img[h] = 127.0
            eps4 = np.maximum(amax_img / 7.0, 1.0)
            out[f"{short}_pack"] = make_rqt(
                1.0 / eps4, 1.0, qmin=-8, qmax=7, acc_bound=127.0
            )
            out[f"{short}_unpack"] = make_rqt(
                eps4, 1.0, acc_bound=8.0
            )
        return out

    # -- integer path -------------------------------------------------------
    BLOCKWISE_THRESHOLD = 4096  # S_q above this -> streaming attention

    def apply_id(self, t, s_x, *, cache=None, pos=None):
        """s_x: (B, S, d) int8 (zp=0).  Returns (int32 wo-acc, cache)."""
        from repro.sharding.hints import hint

        subs = self._sub()
        B, S, _ = s_x.shape
        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        q = subs["wq"].apply_id(t["wq"], s_x)
        k = subs["wk"].apply_id(t["wk"], s_x)
        v = subs["wv"].apply_id(t["wv"], s_x)
        q = apply_rqt(q, t["q_rqt"])
        k = apply_rqt(k, t["k_rqt"])
        v = apply_rqt(v, t["v_rqt"])
        q, k, v = self._shape_qkv(q, k, v, B, S)
        if S > 1:
            q = hint(q, "act_bhsd")
        rot, cos_q, sin_q = self._rope_tables(rope_tables_int)
        positions = _positions(S, pos)
        q = apply_rope_int(q, cos_q, sin_q, positions, rot)
        k = apply_rope_int(k, cos_q, sin_q, positions, rot)

        if cache is not None:
            if "table" in cache:
                from repro.launch import variants

                # int4-packed pools (DESIGN.md §Serving ¶Sub-8-bit
                # KV): a pool whose trailing axis is hd/2 stores two
                # nibbles per cell — thread the per-head pack/unpack
                # requant images through the write and the read
                kv4 = t["kv4"] if cache["k"].shape[-2] != hd else None
                if (variants.get("paged_decode") == "kernel"
                        and variants.get("attn_softmax") != "int"):
                    # fused paged attention (S == 1 decode, S > 1
                    # chunked prefill): no dense logical KV view —
                    # the kernel streams K/V page by page through the
                    # table (the gather path below stays available as
                    # the parity oracle via paged_decode="gather")
                    return self._paged_kernel_attend(
                        t, q, k, v, cache, pos, subs, kv4=kv4
                    )
                k_all, v_all, cache = _paged_cache_update(
                    cache, k, v, pos, kv4=kv4
                )
            else:
                k_all = _cache_write(cache["k"], k, pos)
                v_all = _cache_write(cache["v"], v, pos)
                cache = {"k": k_all, "v": v_all}
            # serving under a mesh profile: pin the arena's kv-head
            # sharding through the write and (paged) gather, so GSPMD
            # neither replicates the returned cache nor round-trips the
            # pools through a dense layout between steps
            cache = _hint_kv_cache(cache)
            k, v = hint(k_all, "kv_heads"), hint(v_all, "kv_heads")
        T = k.shape[2]

        kh = self._expand_kv(k) if S == 1 else hint(
            self._expand_kv(k), "act_bhsd")
        vh = self._expand_kv(v) if S == 1 else hint(
            self._expand_kv(v), "act_bhsd")
        if S > self.BLOCKWISE_THRESHOLD:
            s_ctx = self._blockwise_id(t, q, kh, vh, pos)
        else:
            from repro.launch import variants

            scores = hint(
                jnp.einsum(
                    "bhsd,bhtd->bhst", q, kh,
                    preferred_element_type=jnp.int32,
                ),
                "probs_dec" if S == 1 else "probs",
            )
            if variants.get("attn_softmax") == "int" and "sm_tabs" in t:
                # integer-only softmax: NO float island at all
                from repro.core.intsoftmax import int_softmax

                bmask = _bool_mask(S, T, pos, self.window)
                s_p = hint(
                    int_softmax(scores, t["sm_tabs"], mask=bmask),
                    "probs_dec" if S == 1 else "probs",
                )
            else:
                # ---- float island (paper §3.8: exponentials) ----
                logits = scores.astype(jnp.float32) * t["score_scale"]
                logits = logits + _mask(S, T, pos, self.window)
                probs = hint(
                    jax.nn.softmax(logits, axis=-1),
                    "probs_dec" if S == 1 else "probs",
                )
                s_p = jnp.round(probs * 127.0).astype(jnp.int8)
            # ---- island exit ----
            acc = jnp.einsum(
                "bhst,bhtd->bhsd", s_p, vh, preferred_element_type=jnp.int32
            )
            s_ctx = apply_rqt(acc, t["ctx_rqt"])
        s_ctx = s_ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        return subs["wo"].apply_id(t["wo"], s_ctx), cache

    def _blockwise_id(self, t, q, kh, vh, pos):
        """Streaming (flash-style) ID attention: lax.scan over KV blocks,
        per-block int8 probability images — the jnp twin of the
        quant_attention Pallas kernel (kernels/ref.py algorithm).  Keeps
        prefill memory O(S * block) instead of O(S^2)."""
        B, H, S, hd = q.shape
        T = kh.shape[2]
        blk = self.BLOCKWISE_THRESHOLD // 2
        n_blk = T // blk
        assert T % blk == 0, (T, blk)
        q32 = q.astype(jnp.int32)
        k_blocks = kh.reshape(B, H, n_blk, blk, hd).transpose(2, 0, 1, 3, 4)
        v_blocks = vh.reshape(B, H, n_blk, blk, hd).transpose(2, 0, 1, 3, 4)
        q_pos = _positions(S, pos)

        def body(carry, xs):
            m_run, l_run, acc = carry
            j, kb, vb = xs
            s = jnp.einsum(
                "bhsd,bhtd->bhst",
                q32,
                kb.astype(jnp.int32),
                preferred_element_type=jnp.int32,
            )
            logits = s.astype(jnp.float32) * t["score_scale"]
            k_pos = j * blk + jnp.arange(blk)
            if q_pos.ndim == 2:  # per-slot positions -> (B,1,S,blk)
                k_b, q_b = k_pos[None, None, None, :], q_pos[:, None, :, None]
            else:
                k_b, q_b = k_pos[None, :], q_pos[:, None]
            mask = _visible(k_b, q_b, self.window)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            qp = jnp.round(p * 127.0).astype(jnp.int8)
            pv = jnp.einsum("bhst,bhtd->bhsd", qp, vb,
                            preferred_element_type=jnp.int32)
            corr = jnp.exp(m_run - m_new)
            acc = acc * corr[..., None] + pv.astype(jnp.float32) / 127.0
            l_new = l_run * corr + jnp.sum(qp.astype(jnp.float32), -1) / 127.0
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, S), jnp.float32)
        a0 = jnp.zeros((B, H, S, hd), jnp.float32)
        (m_f, l_f, acc_f), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.arange(n_blk), k_blocks, v_blocks))
        ctx = acc_f / jnp.maximum(l_f, 1e-9)[..., None]
        # quantize into the ctx image space (rqt on the scaled int value:
        # ctx real units = eps_p * eps_v * acc; here acc_f is already
        # p-normalized so ctx = sum(p*v_img): image units of eps_v. The
        # ctx_rqt tables map eps_p*eps_v accumulators; multiply back 127.
        acc_int = jnp.round(ctx * 127.0).astype(jnp.int32)
        return apply_rqt(acc_int, t["ctx_rqt"])

    def _paged_kernel_attend(self, t, q, k, v, cache, pos, subs,
                             kv4=None):
        """Fused paged ID attention (decode and chunked prefill):
        scatter the new column(s) through the page table, then run
        attention straight over the page pools
        (kernels/paged_attention.py) — the dense logical (B, K, T, hd)
        view is never materialized.  Query row s of slot b sits at
        position pos[b] + s (the kernel masks causally per row).  The
        kernel returns the int32 P.V accumulator and the ctx
        requantization stays out here, so the math is bit-exact with
        the gather path.  Under a serving mesh profile the kernel runs
        with a per-shard head range (shard_map over the "model" axis —
        see paged_attention); the math per (slot, head) is unchanged,
        so sharding keeps bit-exactness.  q/k/v: (B, ., S, hd) int8
        post-RoPE.  Returns (int32 wo-acc, cache)."""
        from repro.kernels.paged_attention import paged_attention
        from repro.sharding.hints import profile_mesh

        pos_v, cache = _paged_write(cache, k, v, pos, kv4=kv4)
        cache = _hint_kv_cache(cache)
        kw = {}
        if kv4 is not None:
            # per-head unpack images as (6, K) int32 kernel operands
            # (rows m, s0, lo, hi, d, zp) — the kernel applies the
            # SAME requant formula as apply_rqt, so kernel == gather
            # stays bit-exact at kv_bits=4 too
            kw = dict(
                k_rq=_kv4_operand(kv4["k_unpack"], self.n_kv_heads),
                v_rq=_kv4_operand(kv4["v_unpack"], self.n_kv_heads),
            )
        acc = paged_attention(
            q, cache["k"], cache["v"], cache["table"], pos_v,
            score_scale=t["score_scale"], group=self.group,
            mesh=profile_mesh(), window=self.window or None, **kw)
        s_ctx = apply_rqt(acc, t["ctx_rqt"])
        B, _, S, _ = q.shape
        s_ctx = s_ctx.transpose(0, 2, 1, 3)
        s_ctx = s_ctx.reshape(B, S, self.n_heads * self.head_dim)
        return subs["wo"].apply_id(t["wo"], s_ctx), cache

    # ------------------------------------------------------------------
    def init_cache(self, B: int, max_len: int, rep: Rep, dtype=None):
        K, hd = self.n_kv_heads, self.head_dim
        dt = jnp.int8 if rep is Rep.ID else (dtype or jnp.bfloat16)
        return {
            "k": jnp.zeros((B, K, max_len, hd), dt),
            "v": jnp.zeros((B, K, max_len, hd), dt),
        }

    def axes(self) -> dict:
        return {
            "wq": {"w": ("embed", "heads")},
            "wk": {"w": ("embed", "heads")},
            "wv": {"w": ("embed", "heads")},
            "wo": {"w": ("heads", "embed")},
        }


def _hint_kv_cache(cache):
    """Pin the serving arena's kv-head sharding on a cache dict's K/V
    leaves (slot rows or page pools — both carry the head axis at
    position 1, the "kv_heads" hint kind).  A no-op outside a mesh
    profile, and for leaves the mesh's model axis cannot divide."""
    from repro.sharding.hints import hint

    return {
        kk: hint(vv, "kv_heads") if kk in ("k", "v") else vv
        for kk, vv in cache.items()
    }


def _positions(S: int, pos):
    """Query positions for S new tokens at offset `pos`.

    pos None -> (S,) [prefill at 0]; scalar -> (S,); per-slot vector
    (B,) -> (B, S) [continuous-batching decode, ragged offsets].
    """
    if pos is None:
        return jnp.arange(S)
    pos = jnp.asarray(pos)
    return pos[..., None] + jnp.arange(S)


def _paged_kv_view(pool, table):
    """Gather the logical (B, K, T, hd) KV view through a page table.

    pool: (n_pages + 1, K, hd, page_size); table: (B, pages_per_slot)
    int32 physical page ids (PAGE_NULL entries point at the trash page
    and surface garbage that per-slot masking hides — every position a
    request has written lives in a page its table row owns).
    T = pages_per_slot * page_size (>= the arena's max_len).
    """
    B, pps = table.shape
    x = jnp.take(pool, table.reshape(-1), axis=0)
    x = x.reshape((B, pps) + pool.shape[1:])      # (B, pps, K, hd, ps)
    x = jnp.transpose(x, (0, 2, 1, 4, 3))         # (B, K, pps, ps, hd)
    return x.reshape(x.shape[0], x.shape[1], -1, x.shape[-1])


def _paged_column_write(pool, new, pos, table):
    """Scatter a multi-token chunk (B, K, S, hd) into each row's pages.

    Row b writes positions [pos[b], pos[b] + S): token s lands on page
    table[b, (pos[b] + s) // page_size] at in-page offset
    (pos[b] + s) % page_size.  Positions past the table's logical
    length (rows parked at INACTIVE_POS, or the padded tail of a final
    partial chunk) and PAGE_NULL table entries both land on the shared
    trash page — write order among trash collisions is irrelevant
    because the trash page is never unmasked.

    Prefix caching (DESIGN.md §Prefix-caching ¶Copy-on-write): this
    write path stays copy-on-write-OBLIVIOUS by design.  The arena
    resolves CoW host-side in `touch`/`touch_range` BEFORE any
    dispatch view is built — a table row handed here never names a
    page another row shares or the prefix trie has registered — so
    the scatter needs no refcount checks on the device, and the
    kv-head-sharded pools inherit sharing for free (page ids are
    shard-invariant; only head columns split).
    """
    ps = pool.shape[3]
    B, _, S, _ = new.shape
    pps = table.shape[1]
    positions = pos[:, None] + jnp.arange(S)          # (B, S)
    valid = positions < pps * ps
    blk = jnp.clip(positions // ps, 0, pps - 1)
    page = jnp.take_along_axis(table, blk, axis=1)    # (B, S)
    page = jnp.where(valid, page, PAGE_NULL)
    off = positions % ps
    new_f = jnp.moveaxis(new, 2, 1).reshape((B * S,) + new.shape[1:2]
                                            + new.shape[3:])
    return pool.at[page.reshape(-1), :, :, off.reshape(-1)].set(
        new_f.astype(pool.dtype))


def _kv4_operand(rqt, n_kv_heads: int):
    """A kv4 requant tree as one (6, K) int32 kernel operand: rows
    m, s0, lo, hi, d, zp, each broadcast per kv head (scalar entries
    — a single-head site, or the shared d/zp — repeat across K)."""
    rows = (rqt["m"], rqt["s0"], rqt["lo"], rqt["hi"],
            rqt["d"], rqt["zp"])
    return jnp.stack([
        jnp.broadcast_to(
            jnp.asarray(r, jnp.int32).reshape(-1), (n_kv_heads,)
        )
        for r in rows
    ])


def _kv4_pack_image(x, rqt):
    """int8 KV image -> int4 image in [-8, 7], per-kv-head quanta
    (channel axis 1), with ROUND-TO-NEAREST instead of apply_rqt's
    floor shift: the pack site runs once per token outside any kernel,
    so it can afford the half-quantum bias term — halving the stored
    error of every int4 cell.  The UNPACK side stays the floor-shift
    `apply_rqt` formula, which is what the fused kernel replays, so
    read-path parity is untouched (both paths read the same bytes)."""
    m, d = rqt["m"], rqt["d"]
    lo, hi = rqt["lo"], rqt["hi"]
    if m.ndim == 1 and m.shape[0] > 1 and x.ndim > 1:
        shape = [1] * x.ndim
        shape[1] = -1
        m = m.reshape(shape)
        lo = lo.reshape(shape)
        hi = hi.reshape(shape)
    q = jnp.clip(x.astype(jnp.int32), lo, hi)
    # s0 == 0 by construction (acc_bound=127 at make_rqt time), so the
    # staged shift collapses to one rounding shift by d
    half = jnp.where(
        d > 0, jnp.left_shift(jnp.int32(1), jnp.maximum(d - 1, 0)), 0
    )
    out = jnp.right_shift(q * m + half, d)
    return jnp.clip(out, -8, 7).astype(jnp.int8)


def _paged_write(cache, k, v, pos, kv4=None):
    """Scatter the new K/V column(s) through the page table — the
    write half shared by BOTH paged decode paths (fused kernel and
    write-then-gather oracle), so their parity cannot drift at the
    write.  With `kv4` (int4-packed pools) the int8 columns are
    requantized into [-8, 7] per kv head and nibble-packed along hd
    first — both nibbles of a pool cell belong to one position, so
    the positional scatter below is packing-oblivious.
    Returns (pos_v, new_cache)."""
    pos_v = jnp.asarray(pos)
    if pos_v.ndim != 1:
        raise NotImplementedError(
            "paged KV caches need a per-slot position vector (B,)")
    if kv4 is not None:
        k = pack_int4(_kv4_pack_image(k, kv4["k_pack"]))
        v = pack_int4(_kv4_pack_image(v, kv4["v_pack"]))
    table = cache["table"]
    k_pool = _paged_column_write(cache["k"], k, pos_v, table)
    v_pool = _paged_column_write(cache["v"], v, pos_v, table)
    return pos_v, {"k": k_pool, "v": v_pool, "table": table}


def _paged_cache_update(cache, k, v, pos, kv4=None):
    """Paged cache step: write the new column(s) through the page
    table, then gather the logical dense view (write-then-gather keeps
    the contiguous-path semantics: the view includes the new tokens).
    Single-token oracle decode and multi-token chunked prefill share
    this path.  With `kv4` the gathered packed view is unpacked back
    into the int8 image space through the same per-head requant
    images the fused kernel applies in its page loop, so the two
    paths stay bit-exact at fixed kv_bits.
    Returns (k_view, v_view, new_cache)."""
    _, new_cache = _paged_write(cache, k, v, pos, kv4=kv4)
    table = new_cache["table"]
    k_view = _paged_kv_view(new_cache["k"], table)
    v_view = _paged_kv_view(new_cache["v"], table)
    if kv4 is not None:
        k_view = apply_rqt(
            unpack_int4(k_view), kv4["k_unpack"], channel_axis=1)
        v_view = apply_rqt(
            unpack_int4(v_view), kv4["v_unpack"], channel_axis=1)
    return k_view, v_view, new_cache


def _cache_write(cache, new, pos):
    """Write `new` (B,K,S,hd) at seq offset `pos` into `cache` (B,K,T,hd).

    Single-token decode uses a one-hot masked rewrite: elementwise along
    the (sequence-sharded) cache axis, so GSPMD never reshards the cache
    (dynamic_update_slice at a traced offset forces an involuntary full
    rematerialization — §Perf hillclimb A, iteration 2).  Multi-token
    writes (prefill) keep dynamic_update_slice (offset is the static 0).

    A per-slot `pos` vector (B,) writes each batch row at its own offset:
    one-hot per row for single-token decode, a masked per-row gather for
    multi-token chunks (chunked prefill) — dynamic_update_slice has no
    per-row offsets.  Rows parked at INACTIVE_POS (>= T) write nothing.
    """
    from repro.launch import variants

    S, T = new.shape[2], cache.shape[2]
    pos_v = None if pos is None else jnp.asarray(pos)
    if pos_v is not None and pos_v.ndim == 1:
        if S == 1:
            oh = (jnp.arange(T)[None, :] == pos_v[:, None])
            oh = oh.astype(cache.dtype)[:, None, :, None]   # (B,1,T,1)
            return cache * (1 - oh) + new.astype(cache.dtype) * oh
        # chunked prefill: row b writes positions [pos[b], pos[b] + S).
        # Cache position t takes chunk column t - pos[b] when that lands
        # in [0, S); everything else keeps the old cache value.
        t_rel = jnp.arange(T)[None, :] - pos_v[:, None]     # (B, T)
        valid = (t_rel >= 0) & (t_rel < S)
        idx = jnp.clip(t_rel, 0, S - 1)[:, None, :, None]   # (B,1,T,1)
        gathered = jnp.take_along_axis(
            new.astype(cache.dtype),
            jnp.broadcast_to(idx, cache.shape), axis=2)
        return jnp.where(valid[:, None, :, None], gathered, cache)
    if S == T:
        return new
    if S == 1 and variants.get("kv_update") == "onehot":
        oh = (jnp.arange(T) == pos).astype(cache.dtype)[None, None, :, None]
        return cache * (1 - oh) + new.astype(cache.dtype) * oh
    return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=2)


def _visible(j, i, window: int = 0):
    """Key position j visible to the query at position i: causal, and
    within the last `window` positions when window > 0."""
    keep = j <= i
    if window:
        keep = keep & (j > i - window)
    return keep


def _bool_mask(S: int, T: int, pos, window: int = 0):
    """Causal (and windowed) keep-mask as booleans (integer-softmax
    island).

    (S, T) for shared positions; (B, 1, S, T) for per-slot `pos` (B,) —
    broadcasts against (B, H, S, T) scores either way.
    """
    i = _positions(S, pos)
    j = jnp.arange(T)
    if i.ndim == 2:
        return _visible(j[None, None, None, :], i[:, None, :, None], window)
    return _visible(j[None, :], i[:, None], window)


def _mask(S: int, T: int, pos, window: int = 0):
    """Causal (prefill) or length (decode) mask, f32 (island-side)."""
    return jnp.where(_bool_mask(S, T, pos, window), 0.0,
                     NEG_INF).astype(jnp.float32)
