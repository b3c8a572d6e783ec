"""Mixture-of-Experts: capacity routing for training, dropless grouped
experts for the integer (served) path.

Float path (training, calibration): dispatch is *index-based*
(argsort-free slotting via one-hot cumsum + scatter-drop, then
gathers), so the lowered HLO carries no mostly-zero dispatch einsums — compiled FLOPs stay equal to useful FLOPs, which keeps
the §Roofline MODEL_FLOPS/HLO_FLOPs ratio honest.  Capacity-dropped
tokens lose those expert contributions (their gate mass is simply absent
from the combine — standard Switch semantics).

ID lowering (`apply_id`, the served path): dropless — every routed
(token, expert) pair runs, grouped by expert, in one Pallas kernel per
layer and call (kernels/moe_experts.py), and tokens the caller marks
invalid route nowhere, so no token's output depends on another's.
Router logits are an int32 accumulator; softmax/top-k is a float
island (paper §3.8 — it is an exponential) whose output gates are
requantized to int8 images (eps = 1/127, zp = 0, like attention probs).
Expert FFNs are per-expert W8A8 with shared activation spaces across
experts (per-expert per-channel weight quanta), so the SiLU LUT and all
requant shifts are shared while multipliers stay per-(expert, channel).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.intmath import build_lut
from repro.core.requant import make_rqt
from repro.core.rep import Rep
from repro.layers.common import (
    ACT_QMIN, ActKind, DeployCtx, act_fn, act_fn_np,
)
from repro.layers.linear import QLinear

EPS_GATE = 1.0 / 127.0


@dataclasses.dataclass(frozen=True)
class QMoE:
    d_model: int
    d_ff: int                  # per-expert hidden
    n_experts: int
    top_k: int
    group_size: int = 512
    capacity_factor: float = 1.25
    act: ActKind = ActKind.SILU
    normalize_gates: bool = True
    name: str = "moe"

    def _router(self) -> QLinear:
        return QLinear(self.d_model, self.n_experts)

    def capacity(self, gs: int) -> int:
        c = int(np.ceil(
            self.top_k * self.capacity_factor * gs / self.n_experts))
        return max(4, int(np.ceil(c / 4) * 4))

    # -- init ----------------------------------------------------------------
    def init(self, key) -> dict:
        kr, kg, ku, kd = jax.random.split(key, 4)
        E, d, f = self.n_experts, self.d_model, self.d_ff
        std_in = 1.0 / np.sqrt(d)
        std_out = 1.0 / np.sqrt(f)
        return {
            "router": self._router().init(kr),
            "wg": jax.random.normal(kg, (E, d, f), jnp.float32) * std_in,
            "wu": jax.random.normal(ku, (E, d, f), jnp.float32) * std_in,
            "wd": jax.random.normal(kd, (E, f, d), jnp.float32) * std_out,
        }

    # -- routing (shared between paths; logits float here) --------------------
    def _route(self, logits_f):
        """logits (G, Gs, E) f32 -> gates (G,Gs,k), experts (G,Gs,k) int32,
        slot positions (G,Gs,k) int32, token-for-slot (G,E,C) int32."""
        G, Gs, E = logits_f.shape
        C = self.capacity(Gs)
        probs = jax.nn.softmax(logits_f, axis=-1)
        gates, experts = jax.lax.top_k(probs, self.top_k)  # (G,Gs,k)
        if self.normalize_gates:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        # slotting: flatten token-major so earlier tokens win capacity
        e_flat = experts.reshape(G, Gs * self.top_k)
        oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)        # (G, Gs*k, E)
        pos_flat = jnp.cumsum(oh, axis=1) - 1       # position per expert
        pos = jnp.take_along_axis(
            pos_flat, e_flat[..., None], axis=-1)[..., 0]       # (G, Gs*k)
        keep = pos < C
        # token index for each (expert, slot): scatter with drop
        tok_ids = jnp.repeat(jnp.arange(Gs, dtype=jnp.int32), self.top_k)
        tok_ids = jnp.broadcast_to(tok_ids[None], (G, Gs * self.top_k))

        def scatter_one(e_row, p_row, keep_row, tok_row):
            init = jnp.full((E, C), Gs, jnp.int32)  # Gs = padding sentinel
            p_safe = jnp.where(keep_row, p_row, C)  # out-of-range -> dropped
            return init.at[e_row, p_safe].set(tok_row, mode="drop")

        tok_for_slot = jax.vmap(scatter_one)(e_flat, pos, keep, tok_ids)
        pos = pos.reshape(G, Gs, self.top_k)
        keep = keep.reshape(G, Gs, self.top_k)
        gates = gates * keep.astype(gates.dtype)
        return gates, experts, pos, tok_for_slot, C

    @staticmethod
    def _gather_tokens(x_pad, tok_for_slot):
        """x_pad (G, Gs+1, d); tok_for_slot (G,E,C) -> (G,E,C,d)."""
        return jax.vmap(lambda xp, t: xp[t])(x_pad, tok_for_slot)

    @staticmethod
    def _combine(he_pad, experts, pos, gates):
        """he_pad (G,E,C+1,f); experts/pos (G,Gs,k); gates (G,Gs,k) ->
        (G,Gs,k,f) gathered expert outputs weighted later."""
        def one(he, e_row, p_row):
            return he[e_row, p_row]  # (Gs,k,f)
        return jax.vmap(one)(he_pad, experts, pos)

    def aux_loss(self, logits_f, experts):
        """Switch-style load-balance loss (mean prob * assignment frac)."""
        G, Gs, E = logits_f.shape
        probs = jax.nn.softmax(logits_f, axis=-1)
        me = jnp.mean(probs, axis=1)                      # (G,E)
        oh = jax.nn.one_hot(experts, E, dtype=jnp.float32)
        ce = jnp.mean(jnp.sum(oh, axis=2), axis=1) / self.top_k  # (G,E)
        return E * jnp.mean(jnp.sum(me * ce, axis=-1))

    def _group(self, x):
        T = x.shape[0]
        gs = min(self.group_size, T)
        assert T % gs == 0, (T, gs)
        return x.reshape(T // gs, gs, -1), gs

    def init_qstate(self) -> dict:
        return {"alpha": jnp.float32(-1.0), "beta": jnp.float32(6.0)}

    # -- float path -----------------------------------------------------------
    def apply_float(self, p, x, rep, *, qs=None, calib=None, scope: str = ""):
        """x: (T, d) float (caller flattens batch*seq). -> (y, aux_loss)"""
        from repro.core.pact import pact_act_asymm

        def w3(name):
            w = p[name]
            if rep is Rep.FQ:
                beta = jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-8)
                return _fq_w3(w, beta)  # per-(expert, out-channel) grid + STE
            return w

        xg, gs = self._group(x)
        logits = self._router().apply(p["router"], xg, rep)
        gates, experts, pos, tfs, C = self._route(logits.astype(jnp.float32))
        from repro.sharding.hints import hint

        x_pad = jnp.concatenate(
            [xg, jnp.zeros_like(xg[:, :1])], axis=1)
        xe = hint(self._gather_tokens(x_pad, tfs), "moe_ecd")  # (G,E,C,d)
        g = hint(
            jnp.einsum("gecd,edf->gecf", xe, w3("wg").astype(x.dtype)),
            "moe_ecf",
        )
        u = hint(
            jnp.einsum("gecd,edf->gecf", xe, w3("wu").astype(x.dtype)),
            "moe_ecf",
        )
        ga = act_fn(self.act, g)
        if rep is Rep.FQ and qs is not None:
            ga = pact_act_asymm(ga, qs["alpha"], qs["beta"], 8)
        h = ga * u
        he = hint(
            jnp.einsum("gecf,efd->gecd", h, w3("wd").astype(x.dtype)),
            "moe_ecd",
        )
        if calib is not None:
            calib.observe(f"{scope}{self.name}.gate.pre", g)
            calib.observe(f"{scope}{self.name}.gate", act_fn(self.act, g))
            calib.observe(f"{scope}{self.name}.up", u)
            calib.observe(f"{scope}{self.name}.h", h)
            calib.observe(f"{scope}{self.name}.out", he)
        he_pad = jnp.concatenate(
            [he, jnp.zeros_like(he[:, :, :1])], axis=2)
        pos_safe = jnp.where(gates > 0, pos, C)
        # vectorized combine: ONE gather/scatter pair for all k (the
        # k-loop variant saves memory but multiplies backward dispatch
        # collectives by k — §Perf hillclimb B; memory is handled by
        # gradient accumulation instead)
        yk = self._combine(he_pad, experts, pos_safe, gates)   # (G,Gs,k,d)
        y = jnp.sum(yk * gates[..., None].astype(x.dtype), axis=2)
        aux = self.aux_loss(logits.astype(jnp.float32), experts)
        return y.reshape(x.shape), aux

    # -- transform ------------------------------------------------------------
    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_x: float, zp_x: int
    ) -> Tuple[dict, np.ndarray]:
        t: dict = {}
        ip_r, eps_acc_r = self._router().deploy(p_np["router"], eps_x, zp_x)
        t["router"] = ip_r
        # island entry scale: per-channel (per-expert) accumulator quanta
        t["router_scale"] = eps_acc_r.astype(np.float32)
        E, d, f = self.n_experts, self.d_model, self.d_ff

        def quant_expert(w, axis_in):
            # per-(expert, out-channel) symmetric int8.  Deploy-time
            # round-to-nearest, not floor: expert tables have no FQ
            # grid to stay bit-consistent with (QLinear keeps floor for
            # pact_weight parity), and floor's -eps/2 systematic bias
            # compounds across the three chained expert matmuls — the
            # same deploy-time fix as the CNN thresholds (PR 2).
            amax = np.maximum(np.abs(w).max(axis=axis_in), 1e-8)  # (E, out)
            eps_w = 2.0 * amax / 255.0
            q = np.clip(np.round(w / eps_w[:, None, :]),
                        -128, 127).astype(np.int8)
            return q, eps_w

        wg_q, eps_wg = quant_expert(np.asarray(p_np["wg"], np.float64), 1)
        wu_q, eps_wu = quant_expert(np.asarray(p_np["wu"], np.float64), 1)
        # shared activation spaces across experts
        lo, hi = ctx.range(f"{scope}{self.name}.gate.pre", "attn")
        amax_pre = max(abs(lo), abs(hi), 1e-6)
        eps_pre = 2.0 * amax_pre / 255.0
        t["g_rqt"] = make_rqt(
            eps_wg * eps_x,
            eps_pre,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=d * 127.0 * 127.0,
        )
        lo_g, hi_g = ctx.range(f"{scope}{self.name}.gate", "act_asym")
        eps_gact = (max(hi_g, lo_g + 1e-6) - lo_g) / 255.0
        zp_g = ACT_QMIN - int(round(lo_g / eps_gact))
        t["g_lut"] = build_lut(
            lambda v: act_fn_np(self.act, v), eps_pre, 0, eps_gact, zp_g
        )
        lo_u, hi_u = ctx.range(f"{scope}{self.name}.up", "attn")
        amax_u = max(abs(lo_u), abs(hi_u), 1e-6)
        eps_u = 2.0 * amax_u / 255.0
        t["u_rqt"] = make_rqt(
            eps_wu * eps_x,
            eps_u,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=d * 127.0 * 127.0,
        )
        lo_h, hi_h = ctx.range(f"{scope}{self.name}.h", "attn")
        amax_h = max(abs(lo_h), abs(hi_h), 1e-6)
        eps_h = 2.0 * amax_h / 255.0
        t["h_rqt"] = make_rqt(
            eps_gact * eps_u,
            eps_h,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=float(256 * 128),
        )
        wd_q, eps_wd = quant_expert(np.asarray(p_np["wd"], np.float64), 1)
        t.update(
            {"wg_q": wg_q, "wu_q": wu_q, "wd_q": wd_q, "zp_g": np.int32(zp_g)}
        )
        # expert output -> shared int8 space, then gate-combine
        lo_o, hi_o = ctx.range(f"{scope}{self.name}.out", "resid")
        amax_o = max(abs(lo_o), abs(hi_o), 1e-6)
        eps_o = 2.0 * amax_o / 255.0
        t["o_rqt"] = make_rqt(
            eps_wd * eps_h,
            eps_o,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=f * 127.0 * 127.0,
        )
        # combine: sum_k gate(int8, eps=1/127) * he(int8, eps_o) -> int32
        eps_comb = EPS_GATE * eps_o
        return t, np.asarray([eps_comb])  # layer-wise acc quantum

    # -- integer path ---------------------------------------------------------
    def apply_id(self, t, s_x, valid=None):
        """s_x (T, d) int8 -> int32 accumulator (T, d) in eps_comb units.

        Dropless: every token's top-k experts are computed, with no
        capacity, so a token's output depends on that token alone.
        `valid` (T,) bool: tokens marked False (chunk padding, rows the
        serving engine parks) route nowhere and get a zero output.  The
        kernel computes nothing for their pairs, but the sort, the row
        gathers and the gate-weighted sum still run over all T * k."""
        from repro.kernels.moe_experts import moe_experts

        with jax.named_scope("moe/router"):
            r_acc = self._router().apply_id(t["router"], s_x)
            # ---- float island: softmax + top-k on tiny (T, E) ----
            logits = r_acc.astype(jnp.float32) * t["router_scale"]
            probs = jax.nn.softmax(logits, axis=-1)
            gates, experts = jax.lax.top_k(probs, self.top_k)  # (T, k)
            if self.normalize_gates:
                gates = gates / jnp.maximum(
                    jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
            s_gates = jnp.round(gates * 127.0).astype(jnp.int32)
            # ---- island exit ----
            if valid is not None:
                experts = jnp.where(valid[:, None], experts, self.n_experts)
                s_gates = jnp.where(valid[:, None], s_gates, 0)
        with jax.named_scope("moe/experts"):
            s_o = moe_experts(s_x, experts, t)  # (T, k, d) int8
            return jnp.sum(s_o.astype(jnp.int32) * s_gates[..., None],
                           axis=1)

    def apply(self, p, x, rep, *, qs=None, calib=None, scope=""):
        if rep is Rep.ID:
            return self.apply_id(p, x), None
        return self.apply_float(p, x, rep, qs=qs, calib=calib, scope=scope)

    def axes(self) -> dict:
        return {
            "router": {"w": ("embed", None)},
            "wg": ("experts", "embed", "mlp"),
            "wu": ("experts", "embed", "mlp"),
            "wd": ("experts", "mlp", "embed"),
        }


def _fq_w3(w, beta):
    """FQ restriction of (E, d_in, out) expert weights, beta (E, out).

    Value: the paper's symmetric floor grid; gradient: chi_[-b, b) STE
    (via the stop-gradient identity, equivalent to pact_weight)."""
    b = beta[:, None, :]
    eps = 2.0 * b / 255.0
    q = jnp.clip(jnp.floor(w / eps), -128, 127) * eps
    mask = jnp.logical_and(w >= -b, w < b).astype(w.dtype)
    return jax.lax.stop_gradient(q - mask * w) + mask * w

