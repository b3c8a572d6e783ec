"""Transformer/SSM blocks: pre-norm residual composition of the quantized
layers, with the full FP/FQ -> deploy -> ID lifecycle per block.

Residual-stream contract (DESIGN.md): between blocks the activation is a
*symmetric int8 image* (zp=0) with a per-block-boundary quantum chosen by
the Add operator's calibrated range (Eq. 24).

Cache contract (DESIGN.md §Serving): every attention cache a block
threads is a {'k', 'v'} dict whose leaves carry (batch, ..., seq, ...)
axes in that order — the serving arenas rely on that structure to
scatter prefills per slot and, for the paged arena, to thread a page
"table" next to the KV leaves through lax.scan (layers/attention.py
handles both cache layouts transparently).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Yarn
from repro.layers.add import QAdd
from repro.layers.attention import QAttention
from repro.layers.common import ActKind, DeployCtx
from repro.layers.mlp import QMLP
from repro.layers.moe import QMoE
from repro.layers.norms import QNorm
from repro.layers.ssm import QMamba1, QMamba2


@dataclasses.dataclass(frozen=True)
class DenseBlock:
    """norm1 -> attention -> add -> norm2 -> MLP (or MoE) -> add."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    act: ActKind = ActKind.SILU
    gated: bool = True
    norm: str = "rms"
    norm_bias: bool = False
    rope_base: float = 10000.0
    rope_fraction: float = 1.0
    max_seq: int = 4096
    # MoE (n_experts > 0 replaces the MLP)
    n_experts: int = 0
    top_k: int = 1
    moe_group: int = 512
    shared_expert: bool = False
    # attention kind (configs.base.AttnKind): sliding window, YaRN
    window: int = 0
    yarn: Optional[Yarn] = None

    def _subs(self):
        s = {
            "norm1": QNorm(
                self.d_model, kind=self.norm,
                use_bias=self.norm_bias, name="norm1",
            ),
            "attn": QAttention(
                self.d_model,
                self.n_heads,
                self.n_kv_heads,
                self.head_dim,
                rope_base=self.rope_base,
                rope_fraction=self.rope_fraction,
                max_seq=self.max_seq,
                window=self.window,
                yarn=self.yarn,
            ),
            "add1": QAdd(name="add1"),
            "norm2": QNorm(
                self.d_model, kind=self.norm,
                use_bias=self.norm_bias, name="norm2",
            ),
            "add2": QAdd(name="add2"),
        }
        if self.n_experts > 0:
            s["moe"] = QMoE(self.d_model, self.d_ff, self.n_experts,
                            self.top_k, group_size=self.moe_group,
                            act=self.act)
            if self.shared_expert:
                s["mlp"] = QMLP(self.d_model, self.d_ff, act=self.act,
                                gated=self.gated, name="shared_mlp")
        else:
            s["mlp"] = QMLP(self.d_model, self.d_ff, act=self.act,
                            gated=self.gated)
        return s

    def init(self, key) -> dict:
        subs = self._subs()
        keys = jax.random.split(key, len(subs))
        p = {}
        for (n, lay), k in zip(subs.items(), keys):
            if hasattr(lay, "init"):
                p[n] = lay.init(k)
        return p

    def init_qstate(self) -> dict:
        subs = self._subs()
        qs = {}
        for n in ("mlp", "moe"):
            if n in subs:
                qs[n] = subs[n].init_qstate()
        return qs

    # -- float ---------------------------------------------------------------
    def apply_float(self, p, x, rep, *, qs=None, cache=None, pos=None,
                    calib=None, scope: str = ""):
        from repro.sharding.hints import hint

        subs = self._subs()
        # MoE blocks keep the residual batch-sharded only: seq-sharding
        # would be resharded away at the (token -> expert) grouping every
        # layer (§Perf hillclimb B, iteration 2)
        x = hint(x, "act_bs_only" if self.n_experts > 0 else "act_bsd")
        h = subs["norm1"].apply(p["norm1"], x, rep, calib=calib,
                                scope=scope + "n1.")
        a, cache = subs["attn"].apply_float(p["attn"], h, rep, cache=cache,
                                            pos=pos, calib=calib, scope=scope)
        x = subs["add1"].apply_fp(x, a, calib=calib, scope=scope)
        h = subs["norm2"].apply(p["norm2"], x, rep, calib=calib,
                                scope=scope + "n2.")
        aux = None
        if self.n_experts > 0:
            B, S, D = h.shape
            m, aux = subs["moe"].apply(
                p["moe"],
                h.reshape(B * S, D),
                rep,
                qs=(qs or {}).get("moe"),
                calib=calib,
                scope=scope,
            )
            m = m.reshape(B, S, D)
            if self.shared_expert:
                m = m + subs["mlp"].apply(
                    p["mlp"],
                    h,
                    rep,
                    qs=(qs or {}).get("mlp"),
                    calib=calib,
                    scope=scope + "sh.",
                )
        else:
            m = subs["mlp"].apply(
                p["mlp"],
                h,
                rep,
                qs=(qs or {}).get("mlp"),
                calib=calib,
                scope=scope,
            )
        x = subs["add2"].apply_fp(x, m, calib=calib, scope=scope)
        return x, cache, aux

    # -- transform ------------------------------------------------------------
    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_in: float
    ) -> Tuple[dict, float]:
        subs = self._subs()
        t: dict = {}
        tn1, eps_n1, _ = subs["norm1"].deploy(
            ctx, scope + "n1.", p_np["norm1"], eps_in
        )
        t["norm1"] = tn1
        ta, eps_attn_acc = subs["attn"].deploy(
            ctx, scope, p_np["attn"], eps_n1, 0
        )
        t["attn"] = ta
        tadd1, eps_r1, _ = subs["add1"].deploy(
            ctx, scope, eps_in, 0, eps_attn_acc, 0
        )
        t["add1"] = tadd1
        tn2, eps_n2, _ = subs["norm2"].deploy(
            ctx, scope + "n2.", p_np["norm2"], eps_r1
        )
        t["norm2"] = tn2
        if self.n_experts > 0:
            tm, eps_m_acc = subs["moe"].deploy(
                ctx, scope, p_np["moe"], eps_n2, 0
            )
            t["moe"] = tm
            if self.shared_expert:
                tsh, eps_sh_acc = subs["mlp"].deploy(
                    ctx, scope + "sh.", p_np["mlp"], eps_n2, 0
                )
                t["mlp"] = tsh
                # combine shared + routed in a common int32 space: requant
                # shared acc into the moe comb space before the add
                from repro.core.requant import make_rqt
                t["sh_rqt"] = make_rqt(
                    eps_sh_acc, float(eps_m_acc[0]), zp_out=0,
                    qmin=-(1 << 24), qmax=(1 << 24),
                    requant_factor=ctx.factor,
                    acc_bound=subs["mlp"].d_ff * 127.0 * 127.0)
        else:
            tm, eps_m_acc = subs["mlp"].deploy(
                ctx, scope, p_np["mlp"], eps_n2, 0
            )
            t["mlp"] = tm
        tadd2, eps_r2, _ = subs["add2"].deploy(
            ctx, scope, eps_r1, 0, eps_m_acc, 0
        )
        t["add2"] = tadd2
        return t, eps_r2

    # -- integer --------------------------------------------------------------
    def apply_id(self, t, s_x, *, cache=None, pos=None, valid=None,
                 paged_kernel=True):
        """`valid` (B, S) bool marks the real tokens; the MoE routes the
        others nowhere (None: every token is real).  `paged_kernel`
        goes to the attention (QAttention.apply_id)."""
        from repro.core.requant import apply_rqt
        from repro.sharding.hints import hint

        subs = self._subs()
        s_x = hint(s_x, "act_bs_only" if self.n_experts > 0 else "act_bsd")
        h = subs["norm1"].apply_id(t["norm1"], s_x)
        a_acc, cache = subs["attn"].apply_id(
            t["attn"], h, cache=cache, pos=pos, paged_kernel=paged_kernel
        )
        s_r = subs["add1"].apply_id(t["add1"], s_x, a_acc)
        h = subs["norm2"].apply_id(t["norm2"], s_r)
        if self.n_experts > 0:
            B, S, D = h.shape
            m_acc = subs["moe"].apply_id(
                t["moe"], h.reshape(B * S, D),
                None if valid is None else valid.reshape(B * S))
            m_acc = m_acc.reshape(B, S, D)
            if self.shared_expert:
                sh_acc = subs["mlp"].apply_id(t["mlp"], h)
                m_acc = m_acc + apply_rqt(
                    sh_acc,
                    t["sh_rqt"],
                    qmin=-(1 << 24),
                    qmax=(1 << 24),
                    out_dtype=jnp.int32,
                )
        else:
            m_acc = subs["mlp"].apply_id(t["mlp"], h)
        s_out = subs["add2"].apply_id(t["add2"], s_r, m_acc)
        return s_out, cache

    def init_cache(self, B, max_len, rep, dtype=None):
        return self._subs()["attn"].init_cache(B, max_len, rep, dtype)


@dataclasses.dataclass(frozen=True)
class MambaBlock:
    """norm -> mamba -> add (pre-norm residual SSM block)."""

    d_model: int
    ssm_kind: str = "mamba1"   # "mamba1" | "mamba2"
    d_state: int = 16
    expand: int = 2
    head_dim: int = 64
    norm: str = "rms"

    def _subs(self):
        if self.ssm_kind == "mamba1":
            core = QMamba1(
                self.d_model, d_state=self.d_state, expand=self.expand
            )
        else:
            core = QMamba2(
                self.d_model,
                d_state=self.d_state,
                expand=self.expand,
                head_dim=self.head_dim,
            )
        return {
            "norm": QNorm(self.d_model, kind=self.norm, name="norm"),
            "core": core,
            "add": QAdd(name="add"),
        }

    def init(self, key) -> dict:
        subs = self._subs()
        k1, k2 = jax.random.split(key)
        return {"norm": subs["norm"].init(k1), "core": subs["core"].init(k2)}

    def init_qstate(self) -> dict:
        return {}

    def apply_float(self, p, x, rep, *, qs=None, cache=None, pos=None,
                    calib=None, scope: str = ""):
        from repro.sharding.hints import hint

        subs = self._subs()
        x = hint(x, "act_bs_only")  # SSM cores run L-unsharded (chunking
        # a model-sharded L reshards per chunk); channels carry the model
        # axis instead (ssm_ch)
        h = subs["norm"].apply(
            p["norm"], x, rep, calib=calib, scope=scope + "n."
        )
        y, cache = subs["core"].apply_float(p["core"], h, rep, cache=cache,
                                            calib=calib, scope=scope)
        x = subs["add"].apply_fp(x, y, calib=calib, scope=scope)
        return x, cache, None

    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_in: float
    ) -> Tuple[dict, float]:
        subs = self._subs()
        t = {}
        tn, eps_n, _ = subs["norm"].deploy(
            ctx, scope + "n.", p_np["norm"], eps_in
        )
        t["norm"] = tn
        tc, eps_core_acc = subs["core"].deploy(
            ctx, scope, p_np["core"], eps_n, 0
        )
        t["core"] = tc
        tadd, eps_out, _ = subs["add"].deploy(
            ctx, scope, eps_in, 0, eps_core_acc, 0
        )
        t["add"] = tadd
        return t, eps_out

    def apply_id(self, t, s_x, *, cache=None, pos=None):
        from repro.sharding.hints import hint

        subs = self._subs()
        s_x = hint(s_x, "act_bs_only")
        h = subs["norm"].apply_id(t["norm"], s_x)
        acc, cache = subs["core"].apply_id(t["core"], h, cache=cache)
        # (an RS(int32)+int8-AG decomposition of the out_proj all-reduce
        # was tried and REFUTED: GSPMD keeps the AR and adds a gather —
        # see EXPERIMENTS.md §Perf C-it4; int16-partial AR via shard_map
        # is the designed follow-up)
        s_out = subs["add"].apply_id(t["add"], s_x, acc)
        return s_out, cache

    def init_cache(self, B, max_len, rep, dtype=None):
        return self._subs()["core"].init_cache(B, rep, dtype)


@dataclasses.dataclass(frozen=True)
class SharedAttnBlock:
    """zamba2-style shared attention: attends over concat(x, x0) with
    weights shared across all its applications (passed in, not owned)."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    max_seq: int = 4096
    norm: str = "rms"

    def _subs(self):
        return {
            "norm": QNorm(2 * self.d_model, kind=self.norm, name="norm"),
            "attn": QAttention(
                self.d_model,
                self.n_heads,
                self.n_kv_heads,
                self.head_dim,
                max_seq=self.max_seq,
                d_in=2 * self.d_model,
            ),
            "add": QAdd(name="add"),
        }

    def init(self, key) -> dict:
        subs = self._subs()
        k1, k2 = jax.random.split(key)
        return {"norm": subs["norm"].init(k1), "attn": subs["attn"].init(k2)}

    def init_qstate(self) -> dict:
        return {}

    def apply_float(self, p, x, x0, rep, *, cache=None, pos=None,
                    calib=None, scope: str = ""):
        subs = self._subs()
        cat = jnp.concatenate([x, x0], axis=-1)
        h = subs["norm"].apply(
            p["norm"], cat, rep, calib=calib, scope=scope + "n."
        )
        a, cache = subs["attn"].apply_float(p["attn"], h, rep, cache=cache,
                                            pos=pos, calib=calib, scope=scope)
        x = subs["add"].apply_fp(x, a, calib=calib, scope=scope)
        return x, cache, None

    def deploy(
        self,
        ctx: DeployCtx,
        scope: str,
        p_np: dict,
        eps_in: float,
        eps_x0: float,
    ) -> Tuple[dict, float]:
        from repro.core.requant import make_rqt

        subs = self._subs()
        t = {}
        # unify the two concat halves into one symmetric space
        eps_cat = max(eps_in, eps_x0)
        t["cat_rqt_x"] = make_rqt(
            eps_in,
            eps_cat,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=128.0,
        )
        t["cat_rqt_x0"] = make_rqt(
            eps_x0,
            eps_cat,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=128.0,
        )
        tn, eps_n, _ = subs["norm"].deploy(
            ctx, scope + "n.", p_np["norm"], eps_cat
        )
        t["norm"] = tn
        ta, eps_a_acc = subs["attn"].deploy(ctx, scope, p_np["attn"], eps_n, 0)
        t["attn"] = ta
        tadd, eps_out, _ = subs["add"].deploy(
            ctx, scope, eps_in, 0, eps_a_acc, 0
        )
        t["add"] = tadd
        return t, eps_out

    def apply_id(self, t, s_x, s_x0, *, cache=None, pos=None,
                 paged_kernel=True):
        from repro.core.requant import apply_rqt

        subs = self._subs()
        a_ = apply_rqt(s_x.astype(jnp.int32), t["cat_rqt_x"])
        b_ = apply_rqt(s_x0.astype(jnp.int32), t["cat_rqt_x0"])
        cat = jnp.concatenate([a_, b_], axis=-1)
        h = subs["norm"].apply_id(t["norm"], cat)
        acc, cache = subs["attn"].apply_id(t["attn"], h, cache=cache, pos=pos,
                                           paged_kernel=paged_kernel)
        s_out = subs["add"].apply_id(t["add"], s_x, acc)
        return s_out, cache

    def init_cache(self, B, max_len, rep, dtype=None):
        return self._subs()["attn"].init_cache(B, max_len, rep, dtype)
