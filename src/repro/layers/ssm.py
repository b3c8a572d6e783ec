"""Selective state-space blocks (Mamba-1 / Mamba-2) under NEMO quantization.

Applicability (DESIGN.md §Arch-applicability): the scan core computes
exp(dt*A) — input-dependent exponentials — which the paper's §3.8 assigns
to real-valued fallback.  Everything AROUND the scan is W8A8 integer:
in/x/dt/out projections, the depthwise causal conv, and the SiLU gates.
The island boundary is two static dequant/quant scales.

Scan implementation: chunked associative scan (chunk length bounds the
materialized decay tensors; the recurrence h_t = a_t h_{t-1} + u_t is
associative under (a, u) composition), sequential lax.scan over chunks
carrying the state — O(L) memory with parallel within-chunk depth.

Decode is the O(1) single-step recurrence with (conv-tail, h) in the cache.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.intmath import apply_lut, build_lut
from repro.core.requant import apply_rqt, make_rqt
from repro.core.rep import Rep
from repro.layers.act_quant import QAct
from repro.layers.common import ACT_QMIN, ActKind, DeployCtx, act_fn, act_fn_np
from repro.layers.linear import QLinear

CHUNK = 128


def _assoc_scan(a, u, h0=None):
    """h_t = a_t * h_{t-1} + u_t along axis 1 (time). a/u broadcastable."""
    if h0 is not None:
        u = jnp.concatenate(
            [u[:, :1] + a[:, :1] * h0[:, None], u[:, 1:]], axis=1)

    def comb(x, y):
        ax, ux = x
        ay, uy = y
        return ax * ay, ay * ux + uy

    _, h = jax.lax.associative_scan(comb, (a, u), axis=1)
    return h


def _chunked_scan(a, u):
    """a, u: (B, L, ...) -> h: (B, L, ...), sequential over CHUNK blocks."""
    B, L = a.shape[:2]
    n = max(1, L // CHUNK)
    if L % CHUNK != 0 or L < CHUNK:
        return _assoc_scan(a, u)  # small/ragged: single block
    a_c = a.reshape(B, n, CHUNK, *a.shape[2:]).swapaxes(0, 1)
    u_c = u.reshape(B, n, CHUNK, *u.shape[2:]).swapaxes(0, 1)

    def step(h_prev, au):
        ac, uc = au
        h = _assoc_scan(ac, uc, h0=h_prev)
        return h[:, -1], h

    h0 = jnp.zeros_like(u[:, 0])
    _, hs = jax.lax.scan(step, h0, (a_c, u_c))
    return hs.swapaxes(0, 1).reshape(B, L, *u.shape[2:])


def _chunked_recurrence(inputs, make_au, y_of_h, h_shape, h0=None,
                        checkpoint=True):
    """Memory-bounded selective scan (DESIGN.md §Perf):

    inputs:  pytree of (B, L, ...) tensors (dt, x, B, C ...)
    make_au: chunk-slices -> (a, u) decay/drive tensors (built PER CHUNK —
             the full (B, L, d_inner, d_state) tensors never exist)
    y_of_h:  (h_chunk, chunk_inputs) -> y chunk
    h_shape: state shape (B, ...)

    Returns (y (B, L, ...), h_last).  The chunk body is rematerialized
    (jax.checkpoint), so backward keeps only chunk inputs + carries.
    """
    L = jax.tree.leaves(inputs)[0].shape[1]
    n = max(1, L // CHUNK)
    if L % CHUNK != 0 or L < CHUNK:
        a, u = make_au(inputs)
        h = _assoc_scan(a, u, h0=h0)
        return y_of_h(h, inputs), h[:, -1]
    chunked = jax.tree.map(
        lambda t: t.reshape(t.shape[0], n, CHUNK, *t.shape[2:]
                            ).swapaxes(0, 1), inputs)

    from repro.sharding.hints import hint

    def step(h_prev, xs):
        a, u = make_au(xs)
        h = _assoc_scan(a.astype(jnp.float32), u.astype(jnp.float32),
                        h0=h_prev.astype(jnp.float32))
        # carry in f32 (decay products compound across 256+ chunks),
        # channel-sharded on the model axis (replicated carries force
        # per-chunk data-axis gathers)
        return hint(h[:, -1].astype(jnp.float32), "ssm_h"), y_of_h(h, xs)

    if checkpoint:
        step = jax.checkpoint(step)
    hinit = hint(
        jnp.zeros(h_shape, jnp.float32) if h0 is None else h0, "ssm_h"
    )
    h_last, ys = jax.lax.scan(step, hinit, chunked)
    y = ys.swapaxes(0, 1)
    return y.reshape(y.shape[0], L, *y.shape[3:]), h_last


def _causal_conv1d_fp(x, w, b):
    """x (B, L, D); w (K, D) depthwise; causal."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return y + b


def _causal_conv1d_int(s_x, w_q, b_q, K):
    """int8 x, int8 depthwise w -> int32 accumulator."""
    pad = jnp.pad(s_x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = sum(
        pad[:, i:i + s_x.shape[1], :].astype(jnp.int32)
        * w_q[i].astype(jnp.int32)
        for i in range(K)
    )
    return acc + b_q.astype(jnp.int32)


# ===========================================================================
# Mamba-1  (falcon-mamba-7b)
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class QMamba1:
    d_model: int
    d_state: int = 16
    expand: int = 2
    dt_rank: int = 0          # 0 -> ceil(d_model/16)
    conv_k: int = 4
    name: str = "mamba"

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def rank(self) -> int:
        return self.dt_rank or max(1, int(np.ceil(self.d_model / 16)))

    def _sub(self):
        di, ds, r = self.d_inner, self.d_state, self.rank
        return {
            "in_proj": QLinear(self.d_model, 2 * di),
            "x_proj": QLinear(di, r + 2 * ds),
            "dt_proj": QLinear(self.rank, di, use_bias=True),
            "out_proj": QLinear(di, self.d_model),
        }

    def init(self, key) -> dict:
        subs = self._sub()
        keys = jax.random.split(key, len(subs) + 2)
        p = {n: lay.init(k) for (n, lay), k in zip(subs.items(), keys)}
        di, ds = self.d_inner, self.d_state
        # standard mamba A init: A_log = log(1..ds) per channel
        p["A_log"] = jnp.log(jnp.broadcast_to(
            jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, ds)))
        p["D"] = jnp.ones((di,), jnp.float32)
        p["conv_w"] = jax.random.normal(keys[-2], (self.conv_k, di),
                                        jnp.float32) / np.sqrt(self.conv_k)
        p["conv_b"] = jnp.zeros((di,), jnp.float32)
        # dt bias: softplus^-1 of dt in [1e-3, 1e-1]
        p["dt_proj"]["b"] = jnp.log(jnp.expm1(
            jnp.full((di,), 0.01, jnp.float32)))
        return p

    # -- float scan core ----------------------------------------------------
    def _core_fp(self, x1, dt, B, C, A, D, h0=None, return_h=False):
        """x1 (B?,L,di), dt (.,L,di), B/C (.,L,ds). Returns y (.,L,di).

        The (B, L, di, ds) decay/drive tensors are built chunk-by-chunk
        inside a checkpointed scan; sharding hints keep di on the model
        axis (DESIGN.md memory notes)."""
        from repro.sharding.hints import hint

        Bq = x1.shape[0]
        di, ds = self.d_inner, self.d_state

        def make_au(xs):
            a = hint(jnp.exp(xs["dt"][..., None] * A), "ssm_u")
            u = hint(
                xs["dt"][..., None]
                * xs["B"][..., None, :]
                * xs["x1"][..., None],
                "ssm_u",
            )
            return a, u

        def y_of_h(h, xs):
            return (jnp.sum(h * xs["C"][..., None, :], axis=-1)
                    + D * xs["x1"])

        x1 = hint(x1, "ssm_ch")
        dt = hint(dt, "ssm_ch")
        B = hint(B, "ssm_small")
        C = hint(C, "ssm_small")
        y, h_last = _chunked_recurrence(
            {"x1": x1, "dt": dt, "B": B, "C": C}, make_au, y_of_h,
            (Bq, di, ds), h0=h0)
        if return_h:
            return y, h_last
        return y

    def apply_float(self, p, x, rep, *, cache=None, calib=None, scope=""):
        subs = self._sub()
        di, ds, r = self.d_inner, self.d_state, self.rank
        xz = subs["in_proj"].apply(p["in_proj"], x, rep)
        x1, z = jnp.split(xz, 2, axis=-1)
        if cache is not None:
            conv_in = jnp.concatenate([cache["conv"], x1], axis=1)
            x1c = _causal_conv1d_fp(
                conv_in, p["conv_w"], p["conv_b"])[:, -x1.shape[1]:]
            new_conv = conv_in[:, -(self.conv_k - 1):]
        else:
            x1c = _causal_conv1d_fp(x1, p["conv_w"], p["conv_b"])
            new_conv = x1[:, -(self.conv_k - 1):]
        x1a = act_fn(ActKind.SILU, x1c)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.conv.pre", x1c)
            calib.observe(f"{scope}{self.name}.conv", x1a)
        xdb = subs["x_proj"].apply(p["x_proj"], x1a, rep)
        dt_r, Bm, Cm = jnp.split(xdb, [r, r + ds], axis=-1)
        dt = jax.nn.softplus(subs["dt_proj"].apply(p["dt_proj"], dt_r, rep))
        A = -jnp.exp(p["A_log"])
        h0 = cache["h"] if cache is not None else None
        y, h_last = self._core_fp(
            x1a.astype(jnp.float32),
            dt.astype(jnp.float32),
            Bm.astype(jnp.float32),
            Cm.astype(jnp.float32),
            A,
            p["D"],
            h0=h0,
            return_h=True,
        )
        y = y.astype(x.dtype)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.y", y)
            calib.observe(f"{scope}{self.name}.z.pre", z)
            calib.observe(f"{scope}{self.name}.z", act_fn(ActKind.SILU, z))
            calib.observe(
                f"{scope}{self.name}.gated", y * act_fn(ActKind.SILU, z)
            )
        out = subs["out_proj"].apply(
            p["out_proj"], y * act_fn(ActKind.SILU, z), rep)
        new_cache = (
            {"conv": new_conv, "h": h_last} if cache is not None else None
        )
        return out, new_cache

    # -- transform ------------------------------------------------------------
    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_x: float, zp_x: int
    ) -> Tuple[dict, np.ndarray]:
        subs = self._sub()
        di, ds, r = self.d_inner, self.d_state, self.rank
        t: dict = {}
        nm = f"{scope}{self.name}"
        # in_proj -> split spaces (x1 | z), both symmetric int8
        ip, eps_acc = subs["in_proj"].deploy(p_np["in_proj"], eps_x, zp_x)
        t["in_proj"] = ip
        act_xz = QAct(ActKind.IDENTITY, sym=True, name=f"{self.name}.xz")
        txz, eps_xz, _ = act_xz.deploy(
            ctx, scope, eps_acc, 0, subs["in_proj"].acc_bound()
        )
        t["xz_rqt"] = txz["rqt"]
        # conv (int8 w, per-tap) -> silu LUT
        w = np.asarray(p_np["conv_w"], np.float64)
        amax_w = np.maximum(np.abs(w).max(), 1e-8)
        eps_cw = 2.0 * amax_w / 255.0
        t["conv_wq"] = np.clip(np.floor(w / eps_cw), -128, 127).astype(np.int8)
        eps_cacc = eps_cw * eps_xz
        t["conv_bq"] = np.round(
            np.asarray(p_np["conv_b"], np.float64) / eps_cacc).astype(np.int32)
        lo, hi = ctx.range(f"{nm}.conv.pre", "ssm")
        amax = max(abs(lo), abs(hi), 1e-6)
        eps_cpre = 2.0 * amax / 255.0
        t["conv_rqt"] = make_rqt(
            eps_cacc,
            eps_cpre,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=self.conv_k * 127.0 * 127.0,
        )
        lo_c, hi_c = ctx.range(f"{nm}.conv", "act_asym")
        eps_conv = (max(hi_c, lo_c + 1e-6) - lo_c) / 255.0
        zp_conv = ACT_QMIN - int(round(lo_c / eps_conv))
        t["conv_lut"] = build_lut(
            lambda v: act_fn_np(ActKind.SILU, v),
            eps_cpre,
            0,
            eps_conv,
            zp_conv,
        )
        t["zp_conv"] = np.int32(zp_conv)
        # x_proj consumes the (asym) conv output
        ipx, eps_accx = subs["x_proj"].deploy(
            p_np["x_proj"], eps_conv, zp_conv
        )
        t["x_proj"] = ipx
        act_xdb = QAct(ActKind.IDENTITY, sym=True, name=f"{self.name}.xdb")
        txdb, eps_xdb, _ = act_xdb.deploy(
            ctx, scope, eps_accx, 0, subs["x_proj"].acc_bound()
        )
        t["xdb_rqt"] = txdb["rqt"]
        # dt_proj int8; its accumulator enters the island (softplus)
        ipdt, eps_accdt = subs["dt_proj"].deploy(p_np["dt_proj"], eps_xdb, 0)
        t["dt_proj"] = ipdt
        t["dt_scale"] = eps_accdt.astype(np.float32)  # per-channel (di,)
        # island constants
        t["A"] = -np.exp(np.asarray(p_np["A_log"], np.float32))
        t["Dv"] = np.asarray(p_np["D"], np.float32)
        t["eps_conv_f"] = np.float32(eps_conv)
        t["zp_conv_f"] = np.float32(zp_conv)
        t["eps_xdb_f"] = np.float32(eps_xdb)
        # island exit: y -> symmetric int8
        lo_y, hi_y = ctx.range(f"{nm}.y", "ssm")
        amax_y = max(abs(lo_y), abs(hi_y), 1e-6)
        eps_y = 2.0 * amax_y / 255.0
        t["eps_y_inv"] = np.float32(1.0 / eps_y)
        # gate z: silu LUT on the xz space
        lo_z, hi_z = ctx.range(f"{nm}.z", "act_asym")
        eps_z = (max(hi_z, lo_z + 1e-6) - lo_z) / 255.0
        zp_z = ACT_QMIN - int(round(lo_z / eps_z))
        t["z_lut"] = build_lut(
            lambda v: act_fn_np(ActKind.SILU, v), eps_xz, 0, eps_z, zp_z
        )
        t["zp_z"] = np.int32(zp_z)
        # gated product -> symmetric int8 -> out_proj
        lo_g, hi_g = ctx.range(f"{nm}.gated", "ssm")
        amax_g = max(abs(lo_g), abs(hi_g), 1e-6)
        eps_gt = 2.0 * amax_g / 255.0
        t["gated_rqt"] = make_rqt(
            eps_y * eps_z,
            eps_gt,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=float(256 * 128),
        )
        ipo, eps_acco = subs["out_proj"].deploy(p_np["out_proj"], eps_gt, 0)
        t["out_proj"] = ipo
        return t, eps_acco

    # -- integer path ---------------------------------------------------------
    def apply_id(self, t, s_x, *, cache=None):
        subs = self._sub()
        di, ds, r = self.d_inner, self.d_state, self.rank
        acc = subs["in_proj"].apply_id(t["in_proj"], s_x)
        s_xz = apply_rqt(acc, t["xz_rqt"])
        s_x1, s_z = jnp.split(s_xz, 2, axis=-1)
        if cache is not None:
            conv_in = jnp.concatenate([cache["conv"], s_x1], axis=1)
            c_acc = _causal_conv1d_int(
                conv_in, t["conv_wq"], t["conv_bq"], self.conv_k
            )[:, -s_x1.shape[1]:]
            new_conv = conv_in[:, -(self.conv_k - 1):]
        else:
            c_acc = _causal_conv1d_int(
                s_x1, t["conv_wq"], t["conv_bq"], self.conv_k
            )
            new_conv = s_x1[:, -(self.conv_k - 1):]
        s_cpre = apply_rqt(c_acc, t["conv_rqt"])
        s_conv = apply_lut(s_cpre, t["conv_lut"])         # asym int8
        accx = subs["x_proj"].apply_id(t["x_proj"], s_conv)
        s_xdb = apply_rqt(accx, t["xdb_rqt"])
        s_dtr, s_B, s_C = jnp.split(s_xdb, [r, r + ds], axis=-1)
        acc_dt = subs["dt_proj"].apply_id(t["dt_proj"], s_dtr)
        # ---- float island (paper §3.8: softplus + exp(dt*A) scan) ----
        dt = jax.nn.softplus(acc_dt.astype(jnp.float32) * t["dt_scale"])
        x1f = (s_conv.astype(jnp.float32) - t["zp_conv_f"]) * t["eps_conv_f"]
        Bf = s_B.astype(jnp.float32) * t["eps_xdb_f"]
        Cf = s_C.astype(jnp.float32) * t["eps_xdb_f"]
        h0 = cache["h"] if cache is not None else None
        y, h_last = self._core_fp(
            x1f, dt, Bf, Cf, t["A"], t["Dv"], h0=h0, return_h=True
        )
        s_y = jnp.clip(jnp.round(y * t["eps_y_inv"]), -128, 127).astype(
            jnp.int8
        )
        # ---- island exit ----
        s_zs = apply_lut(s_z, t["z_lut"])
        prod = s_y.astype(jnp.int32) * (s_zs.astype(jnp.int32) - t["zp_z"])
        s_g = apply_rqt(prod, t["gated_rqt"])
        out = subs["out_proj"].apply_id(t["out_proj"], s_g)
        new_cache = (
            {"conv": new_conv, "h": h_last} if cache is not None else None
        )
        return out, new_cache

    def init_cache(self, B: int, rep: Rep, dtype=None):
        di, ds = self.d_inner, self.d_state
        dt = jnp.int8 if rep is Rep.ID else (dtype or jnp.bfloat16)
        return {
            "conv": jnp.zeros((B, self.conv_k - 1, di), dt),
            "h": jnp.zeros((B, di, ds), jnp.float32),
        }

    def apply(self, p, x, rep, *, cache=None, calib=None, scope=""):
        if rep is Rep.ID:
            return self.apply_id(p, x, cache=cache)
        return self.apply_float(p, x, rep, cache=cache, calib=calib,
                                scope=scope)

    def axes(self) -> dict:
        return {
            "in_proj": {"w": ("embed", "heads")},
            "x_proj": {"w": ("heads", None)},
            "dt_proj": {"w": (None, "heads"), "b": ("heads",)},
            "out_proj": {"w": ("heads", "embed")},
            "A_log": ("heads", None),
            "D": ("heads",),
            "conv_w": (None, "heads"),
            "conv_b": ("heads",),
        }


# ===========================================================================
# Mamba-2  (zamba2)
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class QMamba2:
    d_model: int
    d_state: int = 64
    expand: int = 2
    head_dim: int = 64
    conv_k: int = 4
    n_groups: int = 1
    name: str = "mamba2"

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def d_conv_in(self) -> int:
        # conv runs over (x, B, C) as in mamba2
        return self.d_inner + 2 * self.n_groups * self.d_state

    def _sub(self):
        di, ds, H = self.d_inner, self.d_state, self.n_heads
        d_in_proj = 2 * di + 2 * self.n_groups * ds + H
        return {
            "in_proj": QLinear(self.d_model, d_in_proj),
            "out_proj": QLinear(di, self.d_model),
        }

    def init(self, key) -> dict:
        subs = self._sub()
        k1, k2, k3 = jax.random.split(key, 3)
        p = {n: lay.init(k) for (n, lay), k in zip(subs.items(), (k1, k2))}
        H = self.n_heads
        p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32))
        p["D"] = jnp.ones((H,), jnp.float32)
        p["dt_bias"] = jnp.log(jnp.expm1(jnp.full((H,), 0.01, jnp.float32)))
        p["conv_w"] = jax.random.normal(
            k3, (self.conv_k, self.d_conv_in),
            jnp.float32) / np.sqrt(self.conv_k)
        p["conv_b"] = jnp.zeros((self.d_conv_in,), jnp.float32)
        p["norm_g"] = jnp.ones((self.d_inner,), jnp.float32)
        return p

    def _split_proj(self, zxbcdt):
        di, ds, H, G = self.d_inner, self.d_state, self.n_heads, self.n_groups
        z, xBC, dt = jnp.split(zxbcdt, [di, di + di + 2 * G * ds], axis=-1)
        return z, xBC, dt

    def _core_fp(self, xh, dt, Bm, Cm, A, D, h0=None):
        """xh (B,L,H,P); dt (B,L,H); B/C (B,L,G,ds) -> y + last state.

        Per-chunk (B, L, H, P, ds) tensors under a checkpointed scan with
        heads hinted onto the model axis."""
        from repro.sharding.hints import hint

        Bq, L, H, P = xh.shape
        G = self.n_groups
        ds = self.d_state
        # repeat to H and pin the H sharding (mixing replicated B/C with
        # H-sharded xh makes XLA materialize full-L broadcast temps)
        Bm = hint(jnp.repeat(Bm, H // G, axis=2), "ssm_ch")  # (B,L,H,ds)
        Cm = hint(jnp.repeat(Cm, H // G, axis=2), "ssm_ch")

        def make_au(xs):
            a = jnp.exp(xs["dt"] * A)[..., None, None]       # (B,c,H,1,1)
            u = hint(
                xs["dt"][..., None, None]
                * xs["xh"][..., :, None]
                * xs["Bm"][..., None, :],
                "ssm_u2",
            )  # (B,c,H,P,ds)
            return a, u

        def y_of_h(h, xs):
            return (jnp.einsum("blhpn,blhn->blhp", h, xs["Cm"])
                    + D[:, None] * xs["xh"])

        xh = hint(xh, "ssm_ch")
        dt = hint(dt, "ssm_ch")
        y, h_last = _chunked_recurrence(
            {"xh": xh, "dt": dt, "Bm": Bm, "Cm": Cm}, make_au, y_of_h,
            (Bq, H, P, ds), h0=h0)
        return y, h_last

    def apply_float(self, p, x, rep, *, cache=None, calib=None, scope=""):
        subs = self._sub()
        di, ds, H, P = self.d_inner, self.d_state, self.n_heads, self.head_dim
        zxbcdt = subs["in_proj"].apply(p["in_proj"], x, rep)
        z, xBC, dt_r = self._split_proj(zxbcdt)
        if cache is not None:
            conv_in = jnp.concatenate([cache["conv"], xBC], axis=1)
            xBCc = _causal_conv1d_fp(
                conv_in, p["conv_w"], p["conv_b"])[:, -xBC.shape[1]:]
            new_conv = conv_in[:, -(self.conv_k - 1):]
        else:
            xBCc = _causal_conv1d_fp(xBC, p["conv_w"], p["conv_b"])
            new_conv = xBC[:, -(self.conv_k - 1):]
        xBCa = act_fn(ActKind.SILU, xBCc)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.conv.pre", xBCc)
            calib.observe(f"{scope}{self.name}.conv", xBCa)
        x1, Bm, Cm = jnp.split(
            xBCa, [di, di + self.n_groups * ds], axis=-1)
        dt = jax.nn.softplus(dt_r.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        B_, L = x.shape[0], x.shape[1]
        xh = x1.reshape(B_, L, H, P).astype(jnp.float32)
        Bm = Bm.reshape(B_, L, self.n_groups, ds).astype(jnp.float32)
        Cm = Cm.reshape(B_, L, self.n_groups, ds).astype(jnp.float32)
        h0 = cache["h"] if cache is not None else None
        y, h_last = self._core_fp(xh, dt, Bm, Cm, A, p["D"], h0=h0)
        y = y.reshape(B_, L, di).astype(x.dtype)
        # gated RMS norm (mamba2): norm(y * silu(z)) * g
        gated = y * act_fn(ActKind.SILU, z)
        var = jnp.mean(
            jnp.square(gated.astype(jnp.float32)), axis=-1, keepdims=True
        )
        yn = (
            gated.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * p["norm_g"]
        ).astype(x.dtype)
        if calib is not None:
            calib.observe(f"{scope}{self.name}.y", y)
            calib.observe(f"{scope}{self.name}.z.pre", z)
            calib.observe(f"{scope}{self.name}.gated", gated)
            calib.observe(f"{scope}{self.name}.norm", yn)
        out = subs["out_proj"].apply(p["out_proj"], yn, rep)
        new_cache = (
            {"conv": new_conv, "h": h_last} if cache is not None else None
        )
        return out, new_cache

    # -- transform ------------------------------------------------------------
    def deploy(
        self, ctx: DeployCtx, scope: str, p_np: dict, eps_x: float, zp_x: int
    ) -> Tuple[dict, np.ndarray]:

        subs = self._sub()
        di, ds, H = self.d_inner, self.d_state, self.n_heads
        nm = f"{scope}{self.name}"
        t: dict = {}
        ip, eps_acc = subs["in_proj"].deploy(p_np["in_proj"], eps_x, zp_x)
        t["in_proj"] = ip
        act_p = QAct(ActKind.IDENTITY, sym=True, name=f"{self.name}.xz")
        tp, eps_p, _ = act_p.deploy(ctx, scope, eps_acc, 0,
                                    subs["in_proj"].acc_bound())
        t["p_rqt"] = tp["rqt"]
        # conv over xBC
        w = np.asarray(p_np["conv_w"], np.float64)
        eps_cw = 2.0 * max(float(np.abs(w).max()), 1e-8) / 255.0
        t["conv_wq"] = np.clip(np.floor(w / eps_cw), -128, 127).astype(np.int8)
        eps_cacc = eps_cw * eps_p
        t["conv_bq"] = np.round(np.asarray(p_np["conv_b"], np.float64)
                                / eps_cacc).astype(np.int32)
        lo, hi = ctx.range(f"{nm}.conv.pre", "ssm")
        eps_cpre = 2.0 * max(abs(lo), abs(hi), 1e-6) / 255.0
        t["conv_rqt"] = make_rqt(
            eps_cacc,
            eps_cpre,
            zp_out=0,
            requant_factor=ctx.factor,
            acc_bound=self.conv_k * 127.0 * 127.0,
        )
        lo_c, hi_c = ctx.range(f"{nm}.conv", "act_asym")
        eps_conv = (max(hi_c, lo_c + 1e-6) - lo_c) / 255.0
        zp_conv = ACT_QMIN - int(round(lo_c / eps_conv))
        t["conv_lut"] = build_lut(
            lambda v: act_fn_np(ActKind.SILU, v),
            eps_cpre,
            0,
            eps_conv,
            zp_conv,
        )
        # island constants
        t["A"] = -np.exp(np.asarray(p_np["A_log"], np.float32))
        t["Dv"] = np.asarray(p_np["D"], np.float32)
        t["dt_bias"] = np.asarray(p_np["dt_bias"], np.float32)
        t["eps_p_f"] = np.float32(eps_p)
        t["eps_conv_f"] = np.float32(eps_conv)
        t["zp_conv_f"] = np.float32(zp_conv)
        # gated RMS norm runs inside the already-open SSM island (f32) —
        # avoids two stacked int8 stages at the island exit; the island
        # exit quantizes the *norm* output.
        t["norm_g_f"] = np.asarray(p_np["norm_g"], np.float32)
        lo_n, hi_n = ctx.range(f"{nm}.norm", "norm")
        eps_n = 2.0 * max(abs(lo_n), abs(hi_n), 1e-6) / 255.0
        t["eps_n_inv"] = np.float32(1.0 / eps_n)
        ipo, eps_acco = subs["out_proj"].deploy(p_np["out_proj"], eps_n, 0)
        t["out_proj"] = ipo
        return t, eps_acco

    # -- integer path ---------------------------------------------------------
    def apply_id(self, t, s_x, *, cache=None):

        subs = self._sub()
        di, ds, H, P = self.d_inner, self.d_state, self.n_heads, self.head_dim
        acc = subs["in_proj"].apply_id(t["in_proj"], s_x)
        s_all = apply_rqt(acc, t["p_rqt"])
        s_z, s_xBC, s_dt = self._split_proj(s_all)
        if cache is not None:
            conv_in = jnp.concatenate([cache["conv"], s_xBC], axis=1)
            c_acc = _causal_conv1d_int(
                conv_in, t["conv_wq"], t["conv_bq"], self.conv_k
            )[:, -s_xBC.shape[1]:]
            new_conv = conv_in[:, -(self.conv_k - 1):]
        else:
            c_acc = _causal_conv1d_int(
                s_xBC, t["conv_wq"], t["conv_bq"], self.conv_k
            )
            new_conv = s_xBC[:, -(self.conv_k - 1):]
        s_cpre = apply_rqt(c_acc, t["conv_rqt"])
        s_conv = apply_lut(s_cpre, t["conv_lut"])
        # ---- float island: dt softplus + scan ----
        B_, L = s_x.shape[0], s_x.shape[1]
        xBCf = (s_conv.astype(jnp.float32) - t["zp_conv_f"]) * t["eps_conv_f"]
        x1, Bm, Cm = jnp.split(xBCf, [di, di + self.n_groups * ds], axis=-1)
        dt = jax.nn.softplus(
            s_dt.astype(jnp.float32) * t["eps_p_f"] + t["dt_bias"]
        )
        xh = x1.reshape(B_, L, H, P)
        Bm = Bm.reshape(B_, L, self.n_groups, ds)
        Cm = Cm.reshape(B_, L, self.n_groups, ds)
        h0 = cache["h"] if cache is not None else None
        y, h_last = self._core_fp(xh, dt, Bm, Cm, t["A"], t["Dv"], h0=h0)
        y = y.reshape(B_, L, di)
        # gate + gated RMS norm in float (island), quantize at island exit
        zf = s_z.astype(jnp.float32) * t["eps_p_f"]
        gated = y * (zf / (1.0 + jnp.exp(-zf)))
        var = jnp.mean(gated * gated, axis=-1, keepdims=True)
        yn = gated * jax.lax.rsqrt(var + 1e-6) * t["norm_g_f"]
        s_n = jnp.clip(jnp.round(yn * t["eps_n_inv"]), -128, 127).astype(
            jnp.int8
        )
        # ---- island exit ----
        out = subs["out_proj"].apply_id(t["out_proj"], s_n)
        new_cache = (
            {"conv": new_conv, "h": h_last} if cache is not None else None
        )
        return out, new_cache

    def init_cache(self, B: int, rep: Rep, dtype=None):
        dt = jnp.int8 if rep is Rep.ID else (dtype or jnp.bfloat16)
        return {
            "conv": jnp.zeros((B, self.conv_k - 1, self.d_conv_in), dt),
            "h": jnp.zeros(
                (B, self.n_heads, self.head_dim, self.d_state),
                jnp.float32,
            ),
        }

    def apply(self, p, x, rep, *, cache=None, calib=None, scope=""):
        if rep is Rep.ID:
            return self.apply_id(p, x, cache=cache)
        return self.apply_float(p, x, rep, cache=cache, calib=calib,
                                scope=scope)

    def axes(self) -> dict:
        return {
            "in_proj": {"w": ("embed", "heads")},
            "out_proj": {"w": ("heads", "embed")},
            "A_log": (None,),
            "D": (None,),
            "dt_bias": (None,),
            "conv_w": (None, "heads"),
            "conv_b": ("heads",),
            "norm_g": (None,),
        }
