"""Rotary position embeddings, with an integer-only deployment path.

RoPE is a per-position *static* rotation — i.e. a Linear operator with
constant weights — so under the NEMO formalism it quantizes like any other
Linear: the cos/sin tables become int16 integer images with quantum
2^-TRIG_BITS, and the rotation

    q' = q * cos + rotate_half(q) * sin

becomes int8*int16 -> int32 followed by an *exact* requantization (the
table quantum is a power of two, so m=1, d=TRIG_BITS — zero scale error,
only the floor).  Rotations preserve norm, so eps is unchanged.

``fraction`` < 1 rotates only the leading channels (chatglm3's 2d RoPE
applies rotary to half the head dim).

``yarn`` (configs.base.Yarn) blends each frequency between its original
and its interpolated (divided by the factor) value, as YaRN does, with
the blend computed here at deploy time; the table values stay cos/sin,
and YaRN's attention factor is the attention layer's to apply (on the
score scale), so every table entry stays within [-1, 1].
"""
from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np

TRIG_BITS = 14


def inv_freq(rot: int, base: float, yarn=None) -> np.ndarray:
    """(rot / 2,) float64 inverse frequencies; YaRN-blended with `yarn`
    (HF `_compute_yarn_parameters`, truncated correction range)."""
    inv = 1.0 / (base ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    if yarn is None:
        return inv

    def dim_of(n_rot):  # the channel pair that turns n_rot times
        return (rot * math.log(yarn.original_max_position
                               / (n_rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # share of the original (extrapolated) frequency
    return inv / yarn.factor * (1.0 - keep) + inv * keep


@functools.lru_cache(maxsize=32)
def _angles(head_dim: int, max_pos: int, base: float, fraction: float,
            yarn=None):
    rot = int(head_dim * fraction)
    rot -= rot % 2
    inv = inv_freq(rot, base, yarn)
    pos = np.arange(max_pos, dtype=np.float64)
    ang = np.outer(pos, inv)  # (S, rot/2)
    return rot, np.cos(ang), np.sin(ang)


def rope_tables_fp(
    head_dim: int, max_pos: int, base: float = 10000.0, fraction: float = 1.0,
    yarn=None,
):
    rot, cos, sin = _angles(head_dim, max_pos, base, fraction, yarn)
    return rot, jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def rope_tables_int(head_dim: int, max_pos: int, base: float = 10000.0,
                    fraction: float = 1.0, yarn=None):
    rot, cos, sin = _angles(head_dim, max_pos, base, fraction, yarn)
    scale = float(1 << TRIG_BITS)

    def enc(v):
        return jnp.asarray(
            np.clip(np.round(v * scale), -scale, scale - 1), jnp.int16)

    return rot, enc(cos), enc(sin)


def _split(x, rot):
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    return x1, x2, x_pass


def _merge(y1, y2, x_pass):
    y = jnp.stack([y1, y2], axis=-1).reshape(*y1.shape[:-1], -1)
    return jnp.concatenate([y, x_pass], axis=-1) if x_pass.shape[-1] else y


def _gather_trig(cos, sin, positions, dtype):
    """positions (S,) -> trig (S, rot/2); positions (B, S) -> (B, 1, S,
    rot/2) so per-slot decode positions (continuous batching) broadcast
    over the head axis of (B, H, S, hd) activations."""
    c = jnp.take(cos, positions, axis=0).astype(dtype)
    s = jnp.take(sin, positions, axis=0).astype(dtype)
    if positions.ndim == 2:
        c, s = c[:, None], s[:, None]
    return c, s


def apply_rope_fp(x, cos, sin, positions, rot):
    """x: (..., S, head_dim) float; positions: (S,) or (B, S) int."""
    c, s = _gather_trig(cos, sin, positions, x.dtype)
    x1, x2, x_pass = _split(x, rot)
    y1 = x1 * c - x2 * s
    y2 = x1 * s + x2 * c
    return _merge(y1, y2, x_pass)


def apply_rope_int(s_x, cos_q, sin_q, positions, rot):
    """s_x: (..., S, head_dim) int8 (zp=0) -> int8, same quantum.

    positions: (S,) shared, or (B, S) per-slot (continuous batching).
    Accumulator: |x1*c + x2*s| <= 2*127*2^TRIG_BITS < 2^22 (int32-safe);
    exact power-of-two requant with round-to-nearest (+2^(B-1) >> B).
    """
    c, s = _gather_trig(cos_q, sin_q, positions, jnp.int32)
    x1, x2, x_pass = _split(s_x.astype(jnp.int32), rot)
    half = jnp.int32(1 << (TRIG_BITS - 1))
    y1 = jnp.right_shift(x1 * c - x2 * s + half, TRIG_BITS)
    y2 = jnp.right_shift(x1 * s + x2 * c + half, TRIG_BITS)
    y1 = jnp.clip(y1, -128, 127)
    y2 = jnp.clip(y2, -128, 127)
    return _merge(y1, y2, x_pass.astype(jnp.int32)).astype(jnp.int8)
