"""Add operator for converging residual branches (paper §3.5, Eq. 24).

Branches live in their own quantized spaces; one branch is requantized
into the reference space (we use a *fresh* output space wide enough for
the sum rather than naming branch 0 the reference — same formalism,
avoids saturating the residual stream as depth grows):

    Q_s(s) = RQ_{Zb0->Zs}(Q_b0) + RQ_{Zb1->Zs}(Q_b1)

The residual-stream space is symmetric (zp=0) by convention.  NEMO's
requantization_factor for adds defaults to 256 — we inherit that.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.requant import make_rqt
from repro.core.rep import Rep
from repro.layers.common import ACT_QMAX, ACT_QMIN, DeployCtx


@dataclasses.dataclass(frozen=True)
class QAdd:
    name: str = "add"

    def apply_fp(self, a, b, calib=None, scope: str = ""):
        y = a + b
        if calib is not None:
            calib.observe(f"{scope}{self.name}", y)
        return y

    apply_fq = apply_fp

    def deploy(
        self, ctx: DeployCtx, scope: str,
        eps_a: float, zp_a: int, eps_b: float, zp_b: int,
    ) -> Tuple[dict, float, int]:
        """-> (tables, eps_s, zp_s=0)."""
        lo, hi = ctx.range(f"{scope}{self.name}", "resid")
        amax = max(abs(lo), abs(hi), 1e-6)
        eps_s = 2.0 * amax / 255.0
        # requantize each branch into Z_s/2 so the int8 sum cannot wrap:
        # each branch image is clipped to [-64, 63] half-range... instead we
        # sum in int32 and clip once — branch requants output int32 images.
        rq_a = make_rqt(eps_a, eps_s, zp_out=0,
                        qmin=-(1 << 24), qmax=(1 << 24),
                        requant_factor=ctx.factor, acc_bound=float(1 << 16))
        rq_b = make_rqt(eps_b, eps_s, zp_out=0,
                        qmin=-(1 << 24), qmax=(1 << 24),
                        requant_factor=ctx.factor, acc_bound=float(1 << 16))
        return (
            {
                "rq_a": rq_a,
                "rq_b": rq_b,
                "zp_a": np.int32(zp_a),
                "zp_b": np.int32(zp_b),
            },
            eps_s,
            0,
        )

    def apply_id(self, t, s_a, s_b):
        """Branches int8 (any zp) -> symmetric int8 sum (Eq. 24).

        Each branch is requantized with both shifts rounding to nearest
        (half up), not `apply_rqt`'s floor: a floor costs every Add
        half a quantum of downward bias, which compounds along the
        residual stream with depth."""
        qa = s_a.astype(jnp.int32) - t["zp_a"]
        qb = s_b.astype(jnp.int32) - t["zp_b"]
        ya = _requant_round(qa, t["rq_a"])
        yb = _requant_round(qb, t["rq_b"])
        return jnp.clip(ya + yb, ACT_QMIN, ACT_QMAX).astype(jnp.int8)

    def apply(self, t, a, b, rep, *, calib=None, scope=""):
        if rep is Rep.ID:
            return self.apply_id(t, a, b)
        return self.apply_fp(a, b, calib=calib, scope=scope)


def shift_round(x, k):
    """x >> k rounded to nearest, halves up: floor(x / 2^k + 1/2) for
    int32 x and shifts k >= 0 (k may be traced).  Written as the floor
    shift plus the bit below it, so the sum cannot overflow int32."""
    k = jnp.asarray(k, jnp.int32)
    below = jnp.right_shift(x, jnp.maximum(k - 1, 0)) & (k > 0)
    return jnp.right_shift(x, k) + below


def _requant_round(q, rqt):
    """`core.requant.apply_rqt` into the int32 branch space of an Add,
    with the pre-shift `s0` and the final shift `d - s0` rounded."""
    m, d, s0 = rqt["m"], rqt["d"], rqt["s0"]
    q = jnp.clip(q, rqt["lo"], rqt["hi"])
    out = shift_round(shift_round(q, s0) * m, d - s0) + rqt["zp"]
    return jnp.clip(out, -(1 << 24), 1 << 24)
