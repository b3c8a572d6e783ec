"""Integer-only math primitives for the IntegerDeployable path.

These run *inside* jitted ID code, so they must be pure-integer (the jaxpr
audit test enforces it).  Hardware mapping: clz / shifts / mul are native
TPU VPU ops; the Newton isqrt is a short fori_loop of integer divides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def int_isqrt(n):
    """floor(sqrt(n)) for non-negative int32, pure integer.

    Initial guess from the bit length (via count-leading-zeros), then 5
    Newton iterations x <- (x + n//x) >> 1.  Starting at
    2^ceil(bits/2) >= sqrt(n) keeps the iteration monotonically
    decreasing, and quadratic convergence makes 5 steps sufficient for
    32-bit inputs (verified exhaustively-ish in tests).
    """
    n = n.astype(jnp.int32)
    bits = 32 - jax.lax.clz(jnp.maximum(n, 1))
    x0 = jnp.left_shift(jnp.int32(1), (bits + 1) >> 1)  # 2^ceil(bits/2)

    def body(_, x):
        x_new = jnp.right_shift(x + n // jnp.maximum(x, 1), 1)
        return jnp.minimum(x, x_new)  # monotone from above; floor-safe

    x = jax.lax.fori_loop(0, 6, body, x0)
    # Newton can land at floor(sqrt(n))+1 for perfect-square neighbours.
    x = jnp.where(x * x > n, x - 1, x)
    return jnp.where(n <= 0, 0, x).astype(jnp.int32)


def int_reciprocal_q(r, d: int):
    """floor(2^d / r) for positive int32 r — dynamic requant multiplier.

    Used by the integer RMS/LayerNorm (DESIGN.md §3.5): the per-token
    normalizer 1/r enters the multiply-shift chain as this fixed-point
    reciprocal; relative error <= r/2^d.
    """
    r = jnp.maximum(r.astype(jnp.int32), 1)
    return (jnp.int32(1) << d) // r


def build_lut(
    fn,
    eps_in,
    zp_in: int,
    eps_out,
    zp_out: int,
    *,
    qmin: int = -128,
    qmax: int = 127,
) -> np.ndarray:
    """Materialize a pointwise nonlinearity as a 256-entry integer table.

    This is exactly the paper's general staircase quantization function
    (Eq. 8/9): for every stored input level s, thresholds are implied by
    fn's value at real(s).  Host-side float is fine (transform time);
    the runtime op (`apply_lut`) is a pure-integer lookup.
    """
    s = np.arange(qmin, qmax + 1, dtype=np.int64)
    real = (s - zp_in) * float(eps_in)
    y = np.asarray(fn(real), dtype=np.float64)
    t = np.clip(np.round(y / float(eps_out)) + zp_out, qmin, qmax)
    return t.astype(np.int8)


@functools.partial(jax.jit, static_argnames="qmin")
def select_lut(stored, table, *, qmin: int = -128):
    """table[stored - qmin] with no gather, for stored in the table's range.

    A tree of selects: level j picks between neighbouring entries of
    level j-1 by bit j of the index, so a 256-entry table takes 8
    levels and 255 element-wise selects, each against a scalar sliced
    from the traced table (tables are arguments, stacked per layer).
    XLA fuses it into the element-wise loops around it.  On a TPU v5e
    it looks up 32 x 128 x 8192 values in 3.1 ms, where `jnp.take`,
    lowered as a gather, takes 263 ms.  Jitted, so that its ~1,000
    traced ops are traced once per shape and not in every trace of a
    model.
    """
    idx = stored.astype(jnp.int32) - qmin
    n = table.shape[0]
    # pad to a power of two with the last entry; idx never reaches it
    level = [table[min(i, n - 1)] for i in range(1 << (n - 1).bit_length())]
    bit = 0
    while len(level) > 1:
        take_hi = (idx & (1 << bit)) != 0
        level = [
            jnp.where(take_hi, hi, lo)
            for lo, hi in zip(level[::2], level[1::2])
        ]
        bit += 1
    return jnp.broadcast_to(level[0], idx.shape)


def _take_lut(stored, table, *, qmin: int = -128):
    idx = stored.astype(jnp.int32) - qmin
    return jnp.take(table, idx, axis=0)


def apply_lut(stored, table, *, qmin: int = -128):
    """y_stored = table[x_stored - qmin], pure integer.

    Staged by the lowering platform: `select_lut` on a TPU, a gather
    everywhere else (on the CPU the gather is fast and the select tree
    is not).  Both give the same integers.
    """
    return jax.lax.platform_dependent(
        stored,
        jnp.asarray(table),
        tpu=functools.partial(select_lut, qmin=qmin),
        default=functools.partial(_take_lut, qmin=qmin),
    )


def pack_int4(x):
    """Pack int4 values (stored in int8, range [-8, 7]) two per int8
    cell along the LAST axis: element 2i -> low nibble, 2i+1 -> high
    nibble of output cell i (DESIGN.md §Serving ¶Sub-8-bit KV).

    The last axis must be even.  Both nibbles of a cell come from the
    same position along every other axis, so a packed KV pool keeps
    page/table geometry untouched — only head_dim halves.
    """
    if x.shape[-1] % 2:
        raise ValueError(f"last axis must be even, got {x.shape[-1]}")
    lo = x[..., 0::2].astype(jnp.int8)
    hi = x[..., 1::2].astype(jnp.int8)
    return (
        jnp.left_shift(hi, 4) | (lo & jnp.int8(0x0F))
    ).astype(jnp.int8)


def unpack_int4(p):
    """Inverse of pack_int4: int8 cells -> int4 values in [-8, 7]
    (still stored as int8), last axis doubled.  Sign extension via
    shift-left-then-arithmetic-shift-right — pure integer, so it runs
    inside jitted ID code and inside the Pallas page loop alike."""
    p = p.astype(jnp.int8)
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(p.shape[:-1] + (2 * p.shape[-1],))


def avgpool_requant_params(k_total: int, d: int = 15):
    """Eq. 25: 1/(K1*K2) ~= floor(2^d / (K1*K2)) >> d  (integer tables)."""
    m = int((1 << d) // k_total)
    return m, d


def int_avgpool_combine(acc, m: int, d: int):
    """(m * sum + 2^(d-1)) >> d on an int32 pooled sum (Eq. 25).

    The 2^(d-1) bias makes the fixed-point divide round-to-nearest
    instead of floor: still within Eq. 25's 1/2^d error of the exact
    mean, but without floor's half-quantum downward drift — which is
    what low-bitwidth activation images (15-level 4-bit grids) cannot
    afford to lose per pooling stage.
    """
    acc = acc.astype(jnp.int32) * jnp.int32(m) + jnp.int32(1 << (d - 1))
    return jnp.right_shift(acc, d)
